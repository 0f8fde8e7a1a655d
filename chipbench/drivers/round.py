"""Driver of the protocol-round cells: ``DFedRW.run_round``, the program's
own entry, one communication round per call, ending when the round's
device matrix is ready.

Set-up builds the engine on the configuration's data and topology, makes
the n client models on the device from the seed in one jitted call, and
runs the first three rounds through ``run_round``: they compile the round
program and are the rounds the reference replays (their walks, batches,
aggregation plans and keys are recorded on the way into the program's
``execute_round``). The window then continues from the same engine and
state.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from chipbench import flops, traffic
from chipbench.refs import models, round_ref

CHECKED = 3                    # rounds the reference replays


def _dataset(cfg: dict, t: dict, rng: np.random.Generator):
    """Global features and labels, and one index array per client."""
    n, d = cfg["n_clients"], cfg["data"]
    if cfg["kind"] == "fnn":
        x, y = traffic.image_classification(d["n_samples"], d["noise"], d["seed"])
        return x, y, traffic.similarity_partition(y, n, t["u_percent"], rng)
    x, y, owner = traffic.token_stream(n, cfg["seq_len"], d["seqs_per_client"],
                                       cfg["vocab"], d["client_vocab"], d["seed"])
    return x, y, [np.nonzero(owner == c)[0] for c in range(n)]


def _dense(parts):
    """(n, max size) index matrix and mask, short clients tiled."""
    width = max(len(p) for p in parts)
    idx = np.stack([np.tile(p, -(-width // len(p)))[:width] for p in parts]).astype(np.int64)
    mask = np.stack([np.arange(width) < len(p) for p in parts])
    return idx, mask


class Session:
    def __init__(self, cfg: dict, wl: dict, seed: int, chips: int):
        from repro.core import (DFedRW, DFedRWConfig, DFedRWState, QuantConfig,
                                StragglerModel, make_topology)
        from repro.data import FederatedDataset
        from repro.models import make_fnn, make_lstm_lm

        t = wl["traffic"]
        self.cfg, self.bits = cfg, t["bits"]
        rng = np.random.default_rng(seed)
        self.key_rng = np.random.default_rng([seed, 1])
        n = cfg["n_clients"]
        self.x, self.y, parts = _dataset(cfg, t, rng)
        idx, mask = _dense(parts)
        data = FederatedDataset(x=self.x, y=self.y, client_idx=idx, client_mask=mask,
                                n_clients=n)
        if cfg["kind"] == "fnn":
            dims = cfg["dims"]
            model = make_fnn(tuple(dims[1:-1]), in_dim=dims[0], out_dim=dims[-1])
        else:
            model = make_lstm_lm(cfg["vocab"], cfg["embed"], cfg["hidden"], cfg["layers"])
        self.runner = DFedRW(model, data, make_topology(cfg["topology"], n), DFedRWConfig(
            m_chains=cfg["m_chains"], k_walk=cfg["k_walk"], agg_fraction=cfg["agg_fraction"],
            n_agg=cfg["n_agg"], batch_size=cfg["batch_size"], lr_r=cfg["lr_r"],
            lr_q=cfg["lr_q"], quant=QuantConfig(bits=self.bits),
            straggler=StragglerModel(h_percent=t["h_percent"], slowdown=t["slowdown"],
                                     mode=t["straggler_mode"]),
            chain_mode=cfg["chain_mode"], seed=seed))
        lay = models.layout(cfg)
        spec = self.runner.flat_spec
        if (spec.d_pad, tuple(spec.offsets)) != (lay["d_pad"], tuple(lay["offsets"])):
            raise RuntimeError("the engine's flat layout differs from the benchmark's")
        self.wkey = traffic.key_words(rng)

        @jax.jit
        def weights(key):
            vec = models.flatten(models.init(cfg, key), lay)
            return jnp.broadcast_to(vec, (n, lay["d_pad"])), vec

        @jax.jit
        def change_norms(mat, vec):
            d = mat - vec[None]
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(d[:, o:o + s]), axis=1))
                              for o, s in zip(lay["offsets"], lay["sizes"])], axis=1)

        mat, self.v0 = weights(self.wkey)
        starts = rng.integers(0, n, size=cfg["m_chains"]) if cfg["chain_mode"] else None
        self.state = DFedRWState(device_params=mat, chain_starts=starts,
                                 updated=np.zeros(n, dtype=bool))
        self.records, losses, norms = [], [], []
        execute = self.runner.execute_round

        def recording(state, plan, bidx, agg, key, **kw):
            self.records.append({"devices": np.array(plan.devices), "mask": np.array(plan.mask),
                                 "bidx": np.array(bidx), "agg": tuple(np.array(a) for a in agg),
                                 "key": np.array(key), "kbar0": int(state.global_step)})
            self.planned = (plan, bidx, agg, key)
            return execute(state, plan, bidx, agg, key, **kw)

        self.runner.execute_round = recording
        for r in range(CHECKED):
            self.state, met = self.runner.run_round(self.state, traffic.key_words(self.key_rng))
            losses.append(met.train_loss)
            if r in (0, CHECKED - 1):
                norms.append(np.asarray(change_norms(self.state.device_params, self.v0)))
        del self.runner.execute_round
        self.readings = {"losses": losses, "first": norms[0], "third": norms[1],
                         "first_name": "change1_gap"}
        self.failed = 0
        self.recorder = None
        self.counts = {"model_flops_per_call": flops.round_flops(
                           cfg, cfg["m_chains"], cfg["k_walk"], cfg["batch_size"]),
                       "qdq_bytes_per_call": (flops.qdq_round_bytes(
                           lay["d_pad"], cfg["m_chains"], cfg["k_walk"])
                           if self.bits < 32 else None)}

    def call(self) -> int:
        self.state, met = self.runner.run_round(self.state, traffic.key_words(self.key_rng))
        jax.block_until_ready(self.state.device_params)
        self.failed += not np.isfinite(met.train_loss)
        return 1

    def attach_recorder(self) -> None:
        from repro.obs import Recorder

        self.recorder = Recorder()
        self.runner.attach_obs(self.recorder)

    def host_spans(self) -> list:
        """(name, t0, t1) on the perf_counter clock, from the recorder."""
        if self.recorder is None:
            return []
        return [(e["name"], e["t0"], e["t1"]) for e in self.recorder.events
                if e.get("kind") == "span"]

    def memory(self):
        """``memory_analysis()`` of the round program the window runs, on the
        shapes of a checked round (every round has the same shapes)."""
        fn = self.runner.round_program(self.bits)
        return fn.lower(*self.runner.round_inputs(self.state, *self.planned)).compile() \
            .memory_analysis()

    def release(self) -> None:
        self.runner = self.state = self.v0 = self.planned = None

    def reference(self, **kw) -> dict:
        """The plain reference's readings of the checked calls; ``kw``
        (``dtype``, ``precision``, ``batch_frac``) goes to the reference."""
        params0 = jax.jit(lambda k: models.init(self.cfg, k))(self.wkey)
        return round_ref.run(self.cfg, self.bits, params0, self.x, self.y, self.records,
                             **kw)
