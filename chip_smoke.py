"""Smoke run of the device programs on one TPU chip.

  python chip_smoke.py               # one chip: row merge, protocol round, pod step, serving
  python chip_smoke.py --four-chips  # four chips: the 4-pod gossip fed step only

Phases, each a plain function of a config (``tests/test_chip_smoke.py`` runs
them on the CPU at smoke widths):

* ``run_merge_phase``: the row-merge kernel (``repro.kernels.rowmerge``)
  against its row-scatter reference, bit for bit, on writes into the upper
  half of the rows (lower 8-row groups untouched; partial last row blocks
  at n=13, 20), several column blocks, with and without add rows, and the
  32-bit path's two merges.
* ``run_round_phase``: the DFedRW flat engine at the scale of
  ``benchmarks/round_engine_bench.py`` (fnn_mnist 2FNN, n=100, M=8, K=8),
  3 rounds at 32 bits then 3 at 8 bits through one engine. Checks finite
  losses, one compiled program per width, the fused qdq kernel compiled into
  the 8-bit program (``tpu_custom_call``), and the fp32 flat engine against
  ``engine="reference"``.
* ``run_pod_phase``: the launcher's ``pod --fed`` path on one pod, Yi-6B at
  its published widths cut to 2 layers, 3 steps at 32 and at 8 bits.
* ``run_serve_phase``: ``ServeEngine`` on the same model, 4 requests of mixed
  lengths on 2 slots at temperature 0, held token for token to the
  sequential decode path (``launch/serve.py --verify``).
* ``run_gossip_phase`` (``--four-chips``): 4 pods, one per chip, ring gossip
  every step at 32 and 8 bits. The gossiped params are held to the dense
  mixing matrix applied to the pods' pre-gossip params, and the compiled
  step must gossip by collective-permute with no all-reduce.

Weights and data come from ``--seed``. The script refuses to run anywhere
but on a TPU, and its last line is the JSON result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_arch, get_smoke  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402


WIDTHS = (32, 8)                   # wire widths every phase runs: fp32, 8-bit
LR_R = 100.0                       # the pod launcher's default lr schedule


def say(msg: str) -> None:
    print(msg, flush=True)


def _arch(name: str, layers: int, smoke: bool):
    cfg = get_smoke(name) if smoke else get_arch(name)
    return dataclasses.replace(cfg, n_layers=layers)


def _peak_bytes() -> str:
    """The process's peak so far on device 0, as the allocator reports it."""
    stats = jax.devices()[0].memory_stats()
    if not stats or "peak_bytes_in_use" not in stats:
        return "not reported by this backend"
    return f"{stats['peak_bytes_in_use']} ({stats['peak_bytes_in_use'] / 1e9:.3f} GB)"


# ------------------------------------------------------------ protocol round
@dataclasses.dataclass(frozen=True)
class RoundSmoke:
    n: int = 100
    m_chains: int = 8
    k_walk: int = 8
    hidden: tuple = (100,)         # fnn_mnist 2FNN, as the round bench
    n_samples: int = 8000
    rounds: int = 3
    # fp32 flat vs reference engine, max abs param difference. At the TPU's
    # default matmul precision f32 operands go through bf16 passes, and the
    # two engines lay their matmuls out differently: 24 SGD steps leave
    # ~1.5e-4 on O(0.05) params. A protocol error (a wrong row or weight)
    # moves params by O(1e-2).
    tol: float = 1e-3


def run_round_phase(c: RoundSmoke, seed: int) -> dict:
    from repro.core import DFedRW, DFedRWConfig, QuantConfig, make_topology
    from repro.core.heterogeneity import partition_similarity
    from repro.data import FederatedDataset, synthetic_image_classification
    from repro.models import make_fnn

    x, y = synthetic_image_classification(n_samples=c.n_samples, seed=seed,
                                          noise=2.0)
    part = partition_similarity(y, c.n, 50, np.random.default_rng(seed))
    data = FederatedDataset.from_partition(x, y, part)
    topo = make_topology("complete", c.n)
    model = make_fnn(c.hidden)

    def runner(engine):
        return DFedRW(model, data, topo, DFedRWConfig(
            m_chains=c.m_chains, k_walk=c.k_walk, engine=engine, seed=seed))

    flat, ref = runner("flat"), runner("reference")
    flat.prepare_bits(WIDTHS)
    key = jax.random.PRNGKey(seed)
    s_flat, s_ref = flat.init_state(key), ref.init_state(key)
    losses, diff = {}, None
    for bits in WIDTHS:
        losses[bits] = []
        for r in range(c.rounds):
            key, sub = jax.random.split(key)
            plan, bidx = flat.plan_walks(s_flat)
            inputs = (s_flat, plan, bidx, flat.plan_aggregation(plan), sub)
            abstract = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                flat.round_inputs(*inputs))
            t0 = time.perf_counter()
            s_flat, m = flat.execute_round(*inputs, bits=bits)
            jax.block_until_ready(s_flat.device_params)
            ms = (time.perf_counter() - t0) * 1e3
            losses[bits].append(m.train_loss)
            say(f"round: bits={bits} round={r} loss={m.train_loss!r} "
                f"wall_ms={ms!r}{' (includes compile)' if r == 0 else ''}")
            if bits == 32:                 # the reference engine plans alike
                s_ref, _ = ref.run_round(s_ref, sub)
        if bits == 32:
            diff = max(
                float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                for a, b in zip(
                    jax.tree_util.tree_leaves(flat.params_pytree(s_flat)),
                    jax.tree_util.tree_leaves(ref.params_pytree(s_ref))))
            say(f"round: fp32 flat vs reference engine max_abs_diff={diff!r} "
                f"(tol {c.tol!r})")
            assert diff <= c.tol, (diff, c.tol)
    assert all(math.isfinite(v) for vs in losses.values() for v in vs), losses
    assert flat.trace_count == len(WIDTHS), flat.trace_count
    say(f"round: trace_count={flat.trace_count} widths={list(WIDTHS)}")
    text = flat.round_program(WIDTHS[-1]).lower(*abstract).compile().as_text()
    kernel = "tpu_custom_call" in text
    say(f"round: bits={WIDTHS[-1]} program has tpu_custom_call={kernel}")
    if jax.default_backend() == "tpu":  # elsewhere the kernel is interpreted
        assert kernel, "qdq kernel missing from the compiled round program"
    return {"losses": losses, "fp32_diff": diff, "kernel": kernel}


# ------------------------------------------------------------ row merge
@dataclasses.dataclass(frozen=True)
class MergeSmoke:
    # (n, K, M, A, d): d of 1000 lane tiles puts the n=20 cases in several
    # column blocks, the last one partial
    shapes: tuple = ((13, 2, 5, 3, 3 * 128), (20, 2, 10, 4, 1000 * 128),
                     (56, 2, 10, 10, 8 * 128))


def _merge_case(n, k, m, a, d, seed):
    """A round's writes into the upper half of the rows, the partial last
    row block among them, the lower groups untouched: a (K, M) walk with
    ties and inactive writers, its winners' targets (losers at n), and A
    distinct aggregators, the last one a padded id n."""
    from repro.core.flatten import elect_writers

    rng = np.random.default_rng(seed)
    devs = rng.integers(n - n // 2, n, size=k * m)
    mask = rng.random(k * m) < 0.7
    _, wins = elect_writers(jnp.asarray(devs), jnp.asarray(mask), n)
    targets = jnp.where(wins, jnp.asarray(devs), n).reshape(k, m).astype(jnp.int32)
    agg = np.append(n - n // 2 + rng.choice(n // 2, size=a - 1, replace=False), n).astype(np.int32)

    def rand(*shape):
        return jnp.asarray(rng.standard_normal(shape, dtype=np.float32))

    return rand(n, d), targets, rand(k, m, d), jnp.asarray(agg), rand(a, d)


def run_merge_phase(c: MergeSmoke, seed: int) -> dict:
    from repro.kernels.rowmerge import merge_rows
    from repro.kernels.rowmerge.ref import merge_rows_ref

    kernel = jax.jit(merge_rows, donate_argnums=0)
    ref = jax.jit(merge_rows_ref)
    out = {}
    for i, (n, k, m, a, d) in enumerate(c.shapes):
        mat, targets, traj, agg, upd = _merge_case(n, k, m, a, d, seed + i)
        # the 32-bit path: the winners' merge, then averages read from it
        last = ref(mat, targets, traj)
        avg = jax.jit(lambda x: (x[agg % n] + x[(agg + 1) % n]) * 0.5)(last)
        cases = {
            "set": (ref(mat, targets, traj), lambda x: kernel(x, targets, traj)),
            "set_add": (ref(mat, targets, traj, agg, upd),
                        lambda x: kernel(x, targets, traj, agg, upd)),
            "two_merges": (ref(last, agg, avg),
                           lambda x: kernel(kernel(x, targets, traj), agg, avg)),
        }
        for name, (want, run) in cases.items():
            got = run(jnp.array(mat, copy=True))
            bad = int(np.sum(np.any(np.asarray(got) != np.asarray(want), axis=1)))
            changed = int(np.sum(np.any(np.asarray(want) != np.asarray(mat), axis=1)))
            say(f"merge: n={n} K={k} M={m} A={a} d={d} {name}: rows changed={changed} "
                f"rows differing from the scatters={bad}")
            assert bad == 0 and changed > 0, (n, name, bad, changed)
            out[(n, name)] = changed
    return out


# ------------------------------------------------------------ pod fed step
@dataclasses.dataclass(frozen=True)
class PodSmoke:
    arch: str = "yi-6b"
    layers: int = 2                # depth cut to fit one 16 GB chip
    smoke: bool = False            # True: the arch's smoke widths
    batch: int = 4
    seq: int = 512
    steps: int = 3


def run_pod_phase(c: PodSmoke, seed: int) -> dict:
    from repro.launch import train

    cfg = _arch(c.arch, c.layers, c.smoke)
    say(f"pod: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
        f"heads={cfg.n_heads}/{cfg.n_kv_heads} d_ff={cfg.d_ff} "
        f"vocab={cfg.vocab} params={cfg.param_count()} "
        f"batch={c.batch} seq={c.seq}")
    out = {}
    for bits in WIDTHS:
        args = train.parse_args([
            "pod", "--arch", c.arch, "--fed", "--pods", "1",
            "--bits", str(bits), "--steps", str(c.steps),
            "--batch", str(c.batch), "--seq", str(c.seq), "--seed", str(seed)])
        losses = train.pod_main(args, cfg=cfg)
        say(f"pod: bits={bits} losses={losses!r} ln(vocab)={math.log(cfg.vocab)!r}")
        assert all(math.isfinite(v) for v in losses), losses
        # random init: the first loss sits near uniform prediction
        assert abs(losses[0] - math.log(cfg.vocab)) < 1.5, losses[0]
        out[bits] = losses
    say(f"pod: peak_bytes_in_use={_peak_bytes()}")
    return out


# ------------------------------------------------------------ serving
@dataclasses.dataclass(frozen=True)
class ServeSmoke:
    arch: str = "yi-6b"
    layers: int = 2
    smoke: bool = False
    requests: int = 4
    slots: int = 2
    prompt_len: int = 64
    gen: int = 16
    chunk: int = 16


def run_serve_phase(c: ServeSmoke, seed: int) -> dict:
    from repro.launch import serve
    from repro.models import transformer as T
    from repro.serve import EngineConfig, ServeEngine

    cfg = _arch(c.arch, c.layers, c.smoke)
    args = serve.parse_args([
        "--arch", c.arch, "--requests", str(c.requests),
        "--max-concurrency", str(c.slots), "--prompt-len", str(c.prompt_len),
        "--gen", str(c.gen), "--chunk", str(c.chunk), "--mixed",
        "--seed", str(seed)])
    max_len = c.prompt_len + c.gen
    params = T.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)
    eng = ServeEngine(cfg, params, EngineConfig(
        max_concurrency=c.slots, max_len=max_len, chunk=c.chunk,
        dtype=jnp.float32, seed=seed))
    t0 = time.perf_counter()
    results = eng.run(serve.build_requests(args, cfg))
    wall = time.perf_counter() - t0
    bad = serve.verify_sequential(cfg, params, results, max_len)
    for st in results:
        say(f"serve: req {st.request.rid} prompt={len(st.request.prompt)} "
            f"tokens={st.generated}")
    say(f"serve: wall_s={wall!r} (includes compile) traces={eng.trace_counts} "
        f"mismatched_vs_sequential={bad}")
    assert eng.trace_counts == {"prefill": 1, "decode": 1}, eng.trace_counts
    assert not bad, f"engine != sequential decode for requests {bad}"
    say(f"serve: all {len(results)} requests identical to sequential decode; "
        f"peak_bytes_in_use={_peak_bytes()}")
    return {"tokens": {st.request.rid: st.generated for st in results}}


# ------------------------------------------------------------ four chips
@dataclasses.dataclass(frozen=True)
class GossipSmoke:
    arch: str = "yi-6b"
    layers: int = 2
    smoke: bool = False
    pods: int = 4
    batch: int = 4
    seq: int = 256                 # gossip buffers leave less room than one pod
    steps: int = 2


def _dense_mixing(n: int, gossip) -> np.ndarray:
    """W[i, j]: weight receiver pod i gives sender pod j's params."""
    from repro.dist.gossip import mixing_weights

    w = np.zeros((n, n), np.float32)
    for i in range(n):
        for off, wt in mixing_weights(n, gossip):
            w[i, (i + off) % n] += wt
    return w


def run_gossip_phase(c: GossipSmoke, seed: int) -> dict:
    from repro.dist.gossip import GossipConfig, mixing_weights
    from repro.dist.sharding import batch_specs, named
    from repro.dist.steps import make_fed_train_step
    from repro.launch.mesh import make_pod_mesh
    from repro.launch.train import init_fed_state, make_batch

    cfg = _arch(c.arch, c.layers, c.smoke)
    mesh = make_pod_mesh(c.pods)
    g = c.pods
    rng = np.random.default_rng(seed)
    batches = [make_batch(cfg, rng, c.batch, c.seq, lead=(g,))
               for _ in range(c.steps)]
    b_shard = named(batch_specs(batches[0], mesh, fed_axis="pod"), mesh)
    batches = [jax.device_put(b, b_shard) for b in batches]
    keys = jax.random.split(jax.random.PRNGKey(seed), c.steps)
    key0 = jax.random.PRNGKey(seed + 1)   # initial weights

    def build(bits, scheduled=False):
        gossip = GossipConfig(axis="pod", topology="ring", every=1,
                              quant_bits=bits)
        fn, p_specs, _ = make_fed_train_step(
            cfg, mesh, gossip, lr_r=LR_R, remat=False, dtype=jnp.float32,
            scheduled=scheduled)
        return gossip, jax.jit(fn, donate_argnums=(0, 1)), p_specs

    # the pods' step-0 params before any gossip: the scheduled variant of the
    # same step with its gossip switched off
    _, local, p_specs = build(32, scheduled=True)
    with mesh:
        p, v = init_fed_state(cfg, mesh, p_specs, key0)
        p, v, _ = local(p, v, batches[0], jnp.int32(0), jnp.bool_(False),
                        keys[0])
        pre = [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(p)]
    del p, v
    out = {}
    for bits in WIDTHS:
        gossip, step, p_specs = build(bits)
        w = _dense_mixing(g, gossip)
        levels = (1 << (bits - 1)) - 1 if bits < 32 else 0
        with mesh:
            p, v = init_fed_state(cfg, mesh, p_specs, key0)
            text = step.lower(p, v, batches[0], jnp.int32(0),
                              keys[0]).compile().as_text()
            permute, allreduce = "collective-permute" in text, "all-reduce" in text
            say(f"gossip: bits={bits} step has collective-permute={permute} "
                f"all-reduce={allreduce}")
            assert permute and not allreduce, (permute, allreduce)
            losses = []
            for s in range(c.steps):
                t0 = time.perf_counter()
                p, v, pod_losses = step(p, v, batches[s], jnp.int32(s), keys[s])
                pod_losses = np.asarray(pod_losses)
                say(f"gossip: bits={bits} step={s} pod_losses={pod_losses.tolist()!r} "
                    f"wall_s={time.perf_counter() - t0!r}")
                assert np.all(np.isfinite(pod_losses)), pod_losses
                losses.append(pod_losses.tolist())
                if s == 0:
                    worst, worst_bound = _check_mix(
                        [np.asarray(leaf) for leaf in jax.tree_util.tree_leaves(p)],
                        pre, w, levels)
                    say(f"gossip: bits={bits} step 0 params vs dense mixing "
                        f"reference: max_abs_err={worst!r} "
                        f"(largest allowed {worst_bound!r})")
        del p, v
        out[bits] = {"losses": losses, "mix_err": worst}
    say(f"gossip: {g} pods, offsets/weights {mixing_weights(g, gossip)}; "
        f"peak_bytes_in_use(device 0)={_peak_bytes()}")
    return out


def _check_mix(mixed, pre, w, levels):
    """Leafwise: the gossiped params against W applied to the pre-gossip
    per-pod params. At 32 bits within 1e-5 x max(1, max|ref|) (the local
    step runs in two programs that may fuse differently); below, within the
    tolerance of tests/test_gossip.py and within the quantizer's own bound
    (each received payload is off by less than one grid step, max|x|/levels
    per element, for the senders' weights)."""
    worst, worst_bound = 0.0, 0.0
    for out, x in zip(mixed, pre):
        ref = np.tensordot(w, x, axes=(1, 0))
        err = float(np.max(np.abs(out - ref)))
        scale = float(np.max(np.abs(ref)))
        if levels == 0:
            bound = 1e-5 * max(1.0, scale)
        else:
            assert err < 0.05 * scale + 1.0, (err, scale)
            amax = np.max(np.abs(x.reshape(x.shape[0], -1)), axis=1)
            off_diag = w - np.diag(np.diag(w))
            bound = float(np.max(off_diag @ amax)) / levels * 1.001 + 1e-7
        assert err <= bound, (err, bound, out.shape)
        worst, worst_bound = max(worst, err), max(worst_bound, bound)
    return worst, worst_bound


# ------------------------------------------------------------ entry
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-pod gossip fed step, one pod per chip")
    args = ap.parse_args(argv)

    cache = enable_compile_cache()
    devs = jax.devices()
    platform, kind = devs[0].platform, devs[0].device_kind
    if platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is {platform!r}); "
              f"this script runs only on a TPU", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if len(devs) < need:
        print(f"chip_smoke: needs {need} chips, found {len(devs)}",
              file=sys.stderr)
        return 1
    say(f"device: {platform} {kind} x{len(devs)}; jax {jax.__version__}; "
        f"compile cache {cache}")
    phases = ([("gossip", run_gossip_phase, GossipSmoke())] if args.four_chips
              else [("merge", run_merge_phase, MergeSmoke()),
                    ("round", run_round_phase, RoundSmoke()),
                    ("pod", run_pod_phase, PodSmoke()),
                    ("serve", run_serve_phase, ServeSmoke())])
    for name, fn, cfg in phases:
        t0 = time.perf_counter()
        fn(cfg, args.seed)
        say(f"{name}: phase wall_s={time.perf_counter() - t0!r}")
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devs)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
