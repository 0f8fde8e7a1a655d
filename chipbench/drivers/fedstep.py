"""Driver of the pod-step cells: the jitted step of
``dist.steps.make_fed_train_step``, called as the pod launcher's loop
(``launch/train.fed_pod_main``) calls it: a host batch of uniform random
tokens, a ``device_put`` to the batch shardings, a key split, the step,
and the per-pod losses read back, every step.

Set-up makes every pod's weights and zero momentum on the device from the
seed in one jitted call, and runs the first three steps through the same
call: they compile the step and are the steps the reference replays. The
window continues from the same step and state.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from chipbench import flops, traffic
from chipbench.refs import fedstep_ref, models

CHECKED = 3                    # steps the reference replays


class Session:
    def __init__(self, cfg: dict, wl: dict, seed: int, chips: int):
        from repro.dist.gossip import GossipConfig
        from repro.dist.sharding import batch_specs, named
        from repro.dist.steps import make_fed_train_step
        from repro.launch.mesh import make_mesh
        from repro.models.config import ArchConfig

        t = self.t = wl["traffic"]
        self.cfg, g = cfg, t["pods"]
        arch = ArchConfig(name=cfg["name"], n_layers=cfg["num_hidden_layers"],
                          d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
                          n_kv_heads=cfg["num_key_value_heads"],
                          d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
                          rope_theta=cfg["rope_theta"], norm_eps=cfg["rms_norm_eps"])
        self.mesh = make_mesh((g, chips // g, 1), ("pod", "data", "model"),
                              jax.devices()[:chips])
        gossip = GossipConfig(axis="pod", topology=t["topology"], every=t["every"],
                              quant_bits=t["bits"])
        step_fn, p_specs, fed_abstract = make_fed_train_step(
            arch, self.mesh, gossip, lr_r=cfg["lr_r"], beta=cfg["beta"], remat=False,
            dtype=jnp.float32)
        self.step = jax.jit(step_fn, donate_argnums=(0, 1))

        def stacked(key):
            return jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (g, *a.shape)),
                                          models.init(cfg, key))

        rng = np.random.default_rng(seed)
        self.wkey = traffic.key_words(rng)
        mine = jax.eval_shape(stacked, self.wkey)
        if (jax.tree_util.tree_structure(mine) != jax.tree_util.tree_structure(fed_abstract)
                or [a.shape for a in jax.tree_util.tree_leaves(mine)]
                != [a.shape for a in jax.tree_util.tree_leaves(fed_abstract)]):
            raise RuntimeError("the program's parameter tree differs from the benchmark's")
        shard = named(p_specs, self.mesh)
        self.params, self.vel = jax.jit(
            lambda k: (stacked(k), jax.tree_util.tree_map(jnp.zeros_like, stacked(k))),
            out_shardings=(shard, shard))(self.wkey)
        self.rng = rng
        self.key = jnp.asarray(traffic.key_words(rng))
        self.b_shard = named(batch_specs(self._batch_abstract(), self.mesh, fed_axis="pod"),
                             self.mesh)
        self.tokens = g * t["batch"] * t["seq"]
        self.i, self.failed = 0, 0

        def leaf_norms(tree):
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim))))
                              for a in jax.tree_util.tree_leaves(tree)], axis=1)

        change = jax.jit(lambda p, k: leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, p, stacked(k))))
        self.batches, losses = [], []
        for s in range(CHECKED):
            losses.append(self._step())
            if s == 0:
                first = np.asarray(jax.jit(leaf_norms)(self.vel))
        self.readings = {"losses": losses, "first": first,
                         "third": np.asarray(change(self.params, self.wkey)),
                         "first_name": "grad1_gap"}
        self.counts = {"model_flops_per_call": self.tokens * flops.decoder_train_flops_per_token(
            cfg, t["seq"])}

    def _batch_abstract(self):
        t = self.t
        shape = (t["pods"], t["batch"], t["seq"])
        return {"tokens": np.zeros(shape, np.int32), "labels": np.zeros(shape, np.int32)}

    def _step(self) -> np.ndarray:
        t = self.t
        with TraceAnnotation("chipbench/batch"):
            host = traffic.token_batch(self.cfg["vocab_size"], self.rng, t["batch"], t["seq"],
                                       lead=(t["pods"],))
            if len(self.batches) < CHECKED:
                self.batches.append(host)
            batch = jax.device_put(host, self.b_shard)
        with TraceAnnotation("chipbench/step"), self.mesh:
            self.key, sub = jax.random.split(self.key)
            self.params, self.vel, losses = self.step(self.params, self.vel, batch,
                                                      jnp.int32(self.i), sub)
        self.i += 1
        with TraceAnnotation("chipbench/readback"):
            return np.asarray(losses)

    def call(self) -> int:
        losses = self._step()
        self.failed += not np.all(np.isfinite(losses))
        return self.tokens

    def attach_recorder(self) -> None:
        pass

    def host_spans(self) -> list:
        return []

    def memory(self):
        """``memory_analysis()`` of the step the window runs."""
        with self.mesh:
            batch = jax.device_put(self._batch_abstract(), self.b_shard)
            return self.step.lower(self.params, self.vel, batch, jnp.int32(self.i),
                                   self.key).compile().memory_analysis()

    def release(self) -> None:
        self.params = self.vel = self.step = None

    def reference(self, **kw) -> dict:
        """The plain reference's readings of the checked calls; ``kw``
        (``dtype``, ``precision``, ``batch_frac``) goes to the reference."""
        init = jax.jit(lambda k: models.init(self.cfg, k))
        return fedstep_ref.run(self.cfg, self.t, lambda: init(self.wkey), self.batches,
                               **kw)
