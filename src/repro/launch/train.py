"""Training launcher.

Two modes:
- protocol: the paper's federated protocol (DFedRW/QDFedRW/baselines) on
  synthetic federated data -- runs anywhere, this is the reproduction.
- pod: the pod-scale LM train step on the host's devices (smoke-size archs
  on CPU; full archs on a real TPU slice). ``--fed`` runs the decomposed
  DFedRW deployment instead: one model replica per pod-axis device, local
  momentum-SGD steps, gossip averaging every ``--gossip-every`` steps
  (quantized with ``--bits < 32``). With a single host device the pod axis
  has size 1 and gossip degenerates to the identity — set
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` for a real mix.

Examples:
  PYTHONPATH=src python -m repro.launch.train protocol --algo dfedrw --rounds 100
  PYTHONPATH=src python -m repro.launch.train pod --arch yi-6b --smoke --steps 20
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.train pod --arch yi-6b --smoke \
    --fed --gossip-every 2 --bits 8 --steps 20
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def protocol_main(args) -> None:
    import jax

    from repro.core import (
        BaselineConfig, DFedAvg, DFedRW, DFedRWConfig, DSGD, FedAvg,
        QuantConfig, StragglerModel, make_topology, train_loop,
    )
    from repro.core.heterogeneity import partition_similarity
    from repro.data import FederatedDataset, synthetic_image_classification
    from repro.models import make_fnn
    from repro.checkpoint import save_checkpoint

    x, y = synthetic_image_classification(n_samples=8000, seed=0, noise=2.0)
    xt, yt = synthetic_image_classification(n_samples=1000, seed=1, noise=2.0)
    part = partition_similarity(y, args.devices, args.u, np.random.default_rng(7))
    data = FederatedDataset.from_partition(x, y, part)
    topo = make_topology(args.topology, args.devices)
    model = make_fnn((200, 200))
    strag = StragglerModel(h_percent=args.h)
    quant = QuantConfig(bits=args.bits)
    if args.algo == "dfedrw":
        runner = DFedRW(model, data, topo, DFedRWConfig(
            m_chains=args.chains, k_walk=args.epochs, straggler=strag, quant=quant))
    else:
        cls = {"fedavg": FedAvg, "dfedavg": DFedAvg, "dsgd": DSGD}[args.algo]
        runner = cls(model, data, topo, BaselineConfig(
            n_selected=args.devices if args.algo != "fedavg" else args.chains,
            local_epochs=args.epochs, straggler=strag, quant=quant))

    rec = None
    if args.trace and not args.obs:
        raise SystemExit("--trace requires --obs (it augments the obs "
                         "stream with tspan events)")
    if args.obs:
        if not hasattr(runner, "attach_obs"):
            raise SystemExit(f"--obs: --algo {args.algo} exposes no telemetry "
                             f"hooks (supported: dfedrw)")
        from repro.obs import Recorder
        # wall clock: per-round engine spans + Eq. 18 bits. --trace marks the
        # stream trace-capable; the protocol engine itself emits no tspans
        # (causal span trees come from the simulator/serving timelines).
        rec = Recorder(trace=args.trace)
        runner.attach_obs(rec)

    def cb(r, metrics, evald):
        print(f"round {r+1:4d}  loss={metrics.train_loss:.4f} "
              f"acc={evald['accuracy']:.4f} busiest_mb={metrics.comm_bits_busiest_round/8e6:.2f}")

    hist = train_loop(runner, args.rounds, xt, yt,
                      eval_every=max(args.rounds // 20, 1), callback=cb)
    print(f"final: {hist.final()}")
    if rec is not None:
        from repro.obs import provenance
        rec.save(args.obs, provenance=provenance(config=vars(args)),
                 workload="train", algo=args.algo)
        print(f"obs: wrote {args.obs} "
              f"(report: python tools/obs_report.py {args.obs})")
    if args.checkpoint_dir:
        # persist the mean model
        state = runner.init_state(jax.random.PRNGKey(0))  # template
        save_checkpoint(args.checkpoint_dir, args.rounds,
                        {"history_acc": np.array(hist.test_accuracy)})
        print(f"checkpointed to {args.checkpoint_dir}")


def make_batch(cfg, rng: np.random.Generator, batch: int, seq: int,
               lead: tuple = ()) -> dict:
    """Uniform random next-token batch (host numpy): tokens, labels and,
    for archs with a frontend, random frontend embeddings."""
    toks = rng.integers(0, cfg.vocab, size=(*lead, batch, seq + 1))
    out = {"tokens": toks[..., :-1].astype(np.int32),
           "labels": toks[..., 1:].astype(np.int32)}
    if cfg.frontend != "none":
        out["embeds"] = rng.normal(
            size=(*lead, batch, cfg.frontend_tokens, cfg.d_model)
        ).astype(np.float32)
    return out


def pod_main(args, cfg=None) -> list[float]:
    """``pod`` mode; ``cfg`` overrides the ``--arch`` lookup (a depth-cut
    config, say). Returns the per-step losses."""
    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch, get_smoke
    from repro.models import transformer as T

    if cfg is None:
        cfg = get_smoke(args.arch) if args.smoke else get_arch(args.arch)
    key = jax.random.PRNGKey(args.seed)
    rng = np.random.default_rng(args.seed)
    if args.fed:
        return fed_pod_main(args, cfg, key, rng)

    from repro.dist.sharding import named
    from repro.dist.steps import make_train_step
    from repro.launch.mesh import make_host_mesh

    mesh = make_host_mesh(data=len(jax.devices()))

    step_fn, p_specs = make_train_step(cfg, mesh, lr_r=args.lr_r)
    params = jax.jit(lambda k: T.init_params(cfg, k, jnp.float32),
                     out_shardings=named(p_specs, mesh))(key)
    vel = jax.tree_util.tree_map(jnp.zeros_like, params)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    losses = []
    with mesh:
        for step in range(args.steps):
            batch = make_batch(cfg, rng, args.batch, args.seq)
            t0 = time.time()
            params, vel, loss = jitted(params, vel, batch, jnp.int32(step))
            losses.append(float(loss))
            print(f"step {step:3d} loss={losses[-1]:.4f} ({time.time()-t0:.2f}s)")
    print("done")
    return losses


def init_fed_state(cfg, mesh, p_specs, key):
    """(params, vel) of the pod deployment: every pod starts from the same
    float32 model with zero momentum. Built in place by one program, so each
    pod's replica is created on its own devices."""
    import jax
    import jax.numpy as jnp

    from repro.dist.sharding import named
    from repro.models import transformer as T

    g = dict(mesh.shape)["pod"]

    def init(k):
        params = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (g, *l.shape)),
            T.init_params(cfg, k, jnp.float32))
        return params, jax.tree_util.tree_map(jnp.zeros_like, params)

    shard = named(p_specs, mesh)
    return jax.jit(init, out_shardings=(shard, shard))(key)


def fed_pod_main(args, cfg, key, rng) -> list[float]:
    """pod --fed: the decomposed DFedRW deployment on the host's devices."""
    import jax
    import jax.numpy as jnp

    from repro.dist.gossip import GossipConfig, wire_bytes
    from repro.dist.sharding import batch_specs, named
    from repro.dist.steps import make_fed_train_step
    from repro.launch.mesh import make_pod_mesh

    mesh = make_pod_mesh(args.pods)
    g = dict(mesh.shape)["pod"]
    gossip = GossipConfig(axis="pod", topology=args.topology,
                          every=args.gossip_every, quant_bits=args.bits)
    step_fn, p_specs, _ = make_fed_train_step(cfg, mesh, gossip, lr_r=args.lr_r,
                                              remat=False, dtype=jnp.float32)
    params, vel = init_fed_state(cfg, mesh, p_specs, key)
    jitted = jax.jit(step_fn, donate_argnums=(0, 1))
    print(f"fed pod mode: {g} pods x data={dict(mesh.shape)['data']} "
          f"topology={gossip.topology} every={gossip.every} bits={gossip.quant_bits} "
          f"wire_bytes/pod={wire_bytes(params, gossip, g)}")
    b_shard = None  # batch shapes are constant: compute shardings once
    losses = []
    with mesh:
        for step in range(args.steps):
            batch = make_batch(cfg, rng, args.batch, args.seq, lead=(g,))
            if b_shard is None:
                b_shard = named(batch_specs(batch, mesh, fed_axis="pod"), mesh)
            batch = jax.device_put(batch, b_shard)
            key, sub = jax.random.split(key)
            t0 = time.time()
            params, vel, pod_losses = jitted(params, vel, batch,
                                             jnp.int32(step), sub)
            losses.append(float(np.mean(np.asarray(pod_losses))))
            print(f"step {step:3d} loss={losses[-1]:.4f} ({time.time()-t0:.2f}s)")
    leaf = jax.tree_util.tree_leaves(params)[0]
    spread = float(jnp.max(jnp.std(leaf.astype(jnp.float32), axis=0)))
    print(f"done (inter-pod param spread={spread:.5f})")
    return losses


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="mode", required=True)
    p = sub.add_parser("protocol")
    p.add_argument("--algo", default="dfedrw",
                   choices=["dfedrw", "fedavg", "dfedavg", "dsgd"])
    p.add_argument("--rounds", type=int, default=100)
    p.add_argument("--devices", type=int, default=20)
    p.add_argument("--u", type=int, default=50)
    p.add_argument("--h", type=float, default=0.0)
    p.add_argument("--bits", type=int, default=32)
    p.add_argument("--epochs", type=int, default=5)
    p.add_argument("--chains", type=int, default=5)
    p.add_argument("--topology", default="complete")
    p.add_argument("--checkpoint-dir", default="")
    p.add_argument("--obs", default="",
                   help="record a repro.obs telemetry stream (JSONL) here "
                        "(report: python tools/obs_report.py <path>)")
    p.add_argument("--trace", action="store_true",
                   help="with --obs: mark the stream trace-capable (schema "
                        "v2). The protocol engine emits no tspan events — "
                        "use the simulator (launch.sim --trace) or serving "
                        "(launch.serve --trace) for causal span trees")
    q = sub.add_parser("pod")
    q.add_argument("--arch", required=True)
    q.add_argument("--smoke", action="store_true")
    q.add_argument("--steps", type=int, default=10)
    q.add_argument("--batch", type=int, default=4)
    q.add_argument("--seq", type=int, default=64)
    q.add_argument("--lr_r", type=float, default=100.0)
    q.add_argument("--fed", action="store_true",
                   help="DFedRW: per-pod replicas + gossip averaging")
    q.add_argument("--pods", type=int, default=0,
                   help="pod-axis size (0 = all host devices)")
    q.add_argument("--gossip-every", type=int, default=1)
    q.add_argument("--bits", type=int, default=32,
                   help="gossip payload quantization bits (<32 = QDFedRW)")
    q.add_argument("--topology", default="ring",
                   choices=["ring", "expander", "all"])
    q.add_argument("--seed", type=int, default=0,
                   help="seed of the initial weights and the token batches")
    return ap.parse_args(argv)


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    args = parse_args(argv)
    enable_compile_cache()
    (protocol_main if args.mode == "protocol" else pod_main)(args)


if __name__ == "__main__":
    main()
