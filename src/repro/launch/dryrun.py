import os
if __name__ == "__main__":
    # CLI entry (python -m repro.launch.dryrun): the production meshes need
    # 512 virtual host devices, which only the CPU backend provides, and
    # both settings MUST be made before any other import (jax locks the
    # backend and device count at first init). Plain imports of this module
    # (tests/benchmarks using the pure helpers below) must NOT mutate the
    # process environment or device count.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    os.environ["JAX_PLATFORMS"] = "cpu"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, printing memory and cost analysis (the roofline
inputs). No arrays are allocated: params, optimizer state, batches, and
caches are all ShapeDtypeStructs.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch yi-6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--fed]
  PYTHONPATH=src python -m repro.launch.dryrun --arch grok-1-314b --shape train_4k --json out.json

The 512-device placeholder is CLI-only (see the __main__ guard above);
callers that want `dryrun_one` on the production meshes must run this module
as a subprocess (as benchmarks/pod_gossip_roofline.py does), never import it
into a session whose device count matters.
"""
import argparse
import dataclasses
import json
import re
import sys
import time
from typing import Any

import jax
import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_arch
from repro.dist.gossip import GossipConfig
from repro.dist.sharding import batch_specs, cache_specs, named, param_specs
from repro.dist.steps import (make_fed_train_step, make_gossip_step,
                              make_serve_step, make_train_step)
from repro.launch.mesh import HW, make_production_mesh
from repro.models import transformer as T
from repro.models.config import ArchConfig

__all__ = ["SHAPES", "input_specs", "dryrun_one", "collective_bytes", "roofline"]

# ------------------------------------------------------------------- shapes
SHAPES = {
    "train_4k": dict(kind="train", seq_len=4096, global_batch=256),
    "prefill_32k": dict(kind="prefill", seq_len=32768, global_batch=32),
    "decode_32k": dict(kind="decode", seq_len=32768, global_batch=128),
    "long_500k": dict(kind="decode", seq_len=524288, global_batch=1),
}

# Full-attention archs get an explicit sliding-window variant at long_500k
# (DESIGN.md decode-shape policy); SSM/hybrid run natively.
LONG_CTX_WINDOW = 8192


def resolve_cfg(arch_id: str, shape_name: str) -> ArchConfig:
    cfg = get_arch(arch_id)
    if shape_name == "long_500k" and cfg.has_attention and not cfg.sub_quadratic:
        cfg = cfg.with_sliding_window(LONG_CTX_WINDOW)
    return cfg


def optimize_cfg(cfg: ArchConfig, global_batch: int = 0) -> ArchConfig:
    """Beyond-paper perf variant (EXPERIMENTS.md #Perf): batch-parallel
    attention for archs whose head count does not divide the 16-way model
    axis (kills the per-layer resharding collectives)."""
    kw = {}
    if cfg.has_attention and cfg.n_heads % 16 != 0:
        # Full (data, model) batch-parallel attention wins even when the
        # batch pads unevenly (measured: padding 32->256 costs ~4.3x attn
        # FLOPs; the alternative data-only constraint replicates attention
        # over the 16-way model axis, ~16x -- see EXPERIMENTS.md).
        kw["attn_batch_parallel"] = True
    if cfg.has_attention and os.environ.get("REPRO_OPT_BF16_SCORES"):
        kw["attn_logits_bf16"] = True
    return dataclasses.replace(cfg, **kw) if kw else cfg


def scaled_cfg(cfg: ArchConfig, k: int) -> ArchConfig:
    """Same architecture with k blocks after its leading dense layers (and
    proportional encoder depth): used to measure per-scanned-body cost
    exactly (see corrected_costs)."""
    pat = len(cfg.block_pattern)
    kwargs = dict(n_layers=cfg.n_dense_layers + pat * k)
    if cfg.enc_dec:
        enc_per_block = cfg.n_enc_layers // cfg.n_blocks
        kwargs["n_enc_layers"] = max(enc_per_block * k, 1)
    return dataclasses.replace(cfg, **kwargs)


def input_specs(cfg: ArchConfig, shape_name: str, fed_groups: int = 0) -> dict:
    """ShapeDtypeStruct stand-ins for every step input (no allocation)."""
    sh = SHAPES[shape_name]
    b, s = sh["global_batch"], sh["seq_len"]
    i32 = jnp.int32
    f = jnp.bfloat16

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype)

    if sh["kind"] in ("train", "prefill"):
        n_front = cfg.frontend_tokens if cfg.frontend != "none" else 0
        if cfg.enc_dec:
            batch = {
                "tokens": sds((b, s), i32),
                "labels": sds((b, s), i32),
                "embeds": sds((b, n_front, cfg.d_model), f),
            }
        elif n_front > 0:
            s_text = max(s - n_front, 1)
            batch = {
                "tokens": sds((b, s_text), i32),
                "labels": sds((b, s_text), i32),
                "embeds": sds((b, n_front, cfg.d_model), f),
            }
        else:
            batch = {"tokens": sds((b, s), i32), "labels": sds((b, s), i32)}
        if fed_groups > 1:
            assert b % fed_groups == 0, (b, fed_groups)
            batch = jax.tree_util.tree_map(
                lambda l: sds((fed_groups, l.shape[0] // fed_groups, *l.shape[1:]), l.dtype),
                batch,
            )
        return batch
    else:  # decode
        cache = jax.eval_shape(
            lambda: T.init_cache(cfg, b, s, f, enc_len=cfg.frontend_tokens if cfg.enc_dec else 0)
        )
        return {"token": sds((b, 1), i32), "cache": cache}


# -------------------------------------------------------------- HLO parsing
_COLL_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^)]*\)|\S+)\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)",
    re.M,
)
_SHAPE_RE = re.compile(r"(f32|bf16|f16|s32|u32|s8|u8|s16|u16|f64|s64|u64|pred)\[([\d,]*)\]")
_BYTES = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
          "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum output-operand bytes of every collective op in the HLO module."""
    out: dict[str, float] = {}
    for m in _COLL_RE.finditer(hlo_text):
        shape_str, op = m.group(1), m.group(2)
        total = 0
        for sm in _SHAPE_RE.finditer(shape_str):
            dt, dims = sm.group(1), sm.group(2)
            n = 1
            if dims:
                for d in dims.split(","):
                    if d:
                        n *= int(d)
            total += n * _BYTES[dt]
        out[op] = out.get(op, 0.0) + float(total)
    out["total"] = float(sum(v for k, v in out.items() if k != "total"))
    return out


# ------------------------------------------------------------------ dry-run
def roofline(cost: dict, coll: dict, n_chips: int, model_flops: float) -> dict:
    """The three roofline terms (seconds) + diagnostics. `cost` carries
    scan-corrected per-chip {"flops", "bytes"}; collective bytes are parsed
    from the partitioned HLO text (same correction)."""
    flops = float(cost["flops"])
    bytes_acc = float(cost["bytes"])
    # cost_analysis flops are per-device post-SPMD; totals:
    compute_s = flops / HW.PEAK_FLOPS_BF16
    memory_s = bytes_acc / HW.HBM_BW
    coll_s = (coll["total"]) / (HW.ICI_BW * HW.ICI_LINKS)
    dominant = max(
        [("compute", compute_s), ("memory", memory_s), ("collective", coll_s)],
        key=lambda kv: kv[1],
    )[0]
    return {
        "compute_s": compute_s,
        "memory_s": memory_s,
        "collective_s": coll_s,
        "dominant": dominant,
        "hlo_flops_per_chip": flops,
        "hlo_bytes_per_chip": bytes_acc,
        "collective_bytes_per_chip": coll["total"],
        "model_flops": model_flops,
        "useful_flops_ratio": model_flops / max(flops * n_chips, 1.0),
        "collectives": {k: v for k, v in coll.items() if k != "total"},
    }


def model_flops_estimate(cfg: ArchConfig, shape_name: str) -> float:
    """6*N_active*D for train (fwd+bwd), 2*N_active*D for inference."""
    sh = SHAPES[shape_name]
    n_active = cfg.active_param_count()
    if sh["kind"] == "train":
        tokens = sh["global_batch"] * sh["seq_len"]
        return 6.0 * n_active * tokens
    if sh["kind"] == "prefill":
        tokens = sh["global_batch"] * sh["seq_len"]
        return 2.0 * n_active * tokens
    tokens = sh["global_batch"]  # one token per sequence
    return 2.0 * n_active * tokens


def _lower_combo(cfg: ArchConfig, shape_name: str, mesh, fed: bool, unroll: bool):
    """Build + lower + compile one (cfg, shape) on `mesh`. Returns compiled."""
    sh = SHAPES[shape_name]
    multi_pod = "pod" in mesh.shape
    if sh["kind"] in ("train", "prefill") and not fed:
        step_fn, p_specs = make_train_step(cfg, mesh, unroll=unroll)
        abstract = T.abstract_params(cfg)
        vel = abstract  # momentum mirrors params
        batch = input_specs(cfg, shape_name)
        b_specs = batch_specs(batch, mesh)
        in_sh = (
            named(p_specs, mesh),
            named(p_specs, mesh),
            named(b_specs, mesh),
            None,
        )
        if sh["kind"] == "prefill":
            def prefill_fn(params, batch):
                logits, _ = T.forward_train(cfg, params, batch["tokens"],
                                            batch.get("embeds"), remat=False,
                                            unroll=unroll)
                return logits[:, -1, :]

            jitted = jax.jit(prefill_fn, in_shardings=(in_sh[0], in_sh[2]))
            args = (abstract, batch)
        else:
            jitted = jax.jit(step_fn, in_shardings=in_sh)
            args = (abstract, vel, batch, jnp.int32(0))
    elif sh["kind"] == "train" and fed:
        # Decomposed DFedRW deployment: this lowers the GOSSIP program only
        # (the per-pod local step is exactly the single-pod baseline
        # train_step -- no cross-pod collectives by construction; see
        # make_gossip_step). GossipConfig.every does not change this
        # program; the combined per-step fed roofline (baseline +
        # gossip/every) is assembled by benchmarks/pod_gossip_roofline.py
        # from the two separate dry-runs.
        assert multi_pod, "fed mode gossips over the pod axis"
        gossip = GossipConfig(axis="pod", topology="ring",
                              quant_bits=int(os.environ.get("REPRO_FED_BITS", "32")))
        gstep, p_specs, fed_abstract = make_gossip_step(cfg, mesh, gossip)
        jitted = jax.jit(gstep, in_shardings=(named(p_specs, mesh), None))
        args = (fed_abstract, jax.random.PRNGKey(0))
    else:  # decode
        serve_fn, p_specs = make_serve_step(cfg, mesh, unroll=unroll)
        abstract = T.abstract_params(cfg)
        spec = input_specs(cfg, shape_name)
        c_specs = cache_specs(spec["cache"], mesh)
        in_sh = (
            named(p_specs, mesh),
            named(c_specs, mesh),
            None,
        )
        jitted = jax.jit(serve_fn, in_shardings=in_sh, donate_argnums=(1,))
        args = (abstract, spec["cache"], spec["token"])

    with mesh:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return compiled


def _raw_costs(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):  # jax<=0.4.x returns [dict]
        cost = cost[0] if cost else {}
    flops = float(cost.get("flops", 0.0))
    bytes_acc = sum(float(v) for k, v in cost.items() if k.startswith("bytes accessed"))
    coll = collective_bytes(compiled.as_text())
    return {"flops": flops, "bytes": bytes_acc, "coll": coll}


def corrected_costs(cfg: ArchConfig, shape_name: str, mesh, fed: bool) -> dict:
    """cost_analysis counts a scanned (while-loop) body ONCE regardless of
    trip count. Correction: lower the same arch at k=2 and k=3 blocks with
    the scan fully unrolled; body cost = C(k3) - C(k2); whole-model cost =
    C(k2) + (n_blocks - 2) * body. Applies to FLOPs, bytes, and collective
    bytes alike (validated in tests/test_dryrun.py). Anchored at k=2/k=3
    (not k=1/k=2): XLA lowers depth-1 stacks specially (measured: k=1 has
    *higher* bytes than k=2), so the k=2->k=3 delta is the first clean
    per-body increment — growth is linear from there on."""
    c1 = _raw_costs(_lower_combo(scaled_cfg(cfg, 2), shape_name, mesh, fed, unroll=True))
    c2 = _raw_costs(_lower_combo(scaled_cfg(cfg, 3), shape_name, mesh, fed, unroll=True))
    # n_blocks == 1 (smoke-size configs) would subtract a body from C(2);
    # clamp so the estimate degrades to C(2) (a slight over-estimate)
    # instead of going negative-corrected.
    n = max(cfg.n_blocks, 2)

    def fix(a, b):
        body = max(b - a, 0.0)
        return a + (n - 2) * body

    coll = {}
    keys = set(c1["coll"]) | set(c2["coll"])
    for k in keys:
        coll[k] = fix(c1["coll"].get(k, 0.0), c2["coll"].get(k, 0.0))
    coll["total"] = float(sum(v for k, v in coll.items() if k != "total"))
    return {
        "flops": fix(c1["flops"], c2["flops"]),
        "bytes": fix(c1["bytes"], c2["bytes"]),
        "coll": coll,
    }


def dryrun_one(arch_id: str, shape_name: str, *, multi_pod: bool = False,
               fed: bool = False, opt: bool = False, verbose: bool = True) -> dict:
    cfg = resolve_cfg(arch_id, shape_name)
    if opt:
        cfg = optimize_cfg(cfg, global_batch=SHAPES[shape_name]["global_batch"])
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()

    # 1) The real thing: full depth, rolled scan -- proves lower+compile.
    compiled = _lower_combo(cfg, shape_name, mesh, fed, unroll=False)
    mem = compiled.memory_analysis()

    # 2) Roofline inputs: scan-corrected per-chip costs (see corrected_costs).
    cc = corrected_costs(cfg, shape_name, mesh, fed)
    rl = roofline(cc, cc["coll"], n_chips, model_flops_estimate(cfg, shape_name))

    result = {
        "arch": arch_id,
        "shape": shape_name,
        "mesh": ("2x16x16" if multi_pod else "16x16"),
        "fed": fed,
        "opt": opt,
        "sliding_window": cfg.sliding_window,
        "params_b": cfg.param_count() / 1e9,
        "active_params_b": cfg.active_param_count() / 1e9,
        "bytes_per_device": {
            "argument": getattr(mem, "argument_size_in_bytes", 0),
            "output": getattr(mem, "output_size_in_bytes", 0),
            "temp": getattr(mem, "temp_size_in_bytes", 0),
            "peak": getattr(mem, "peak_memory_in_bytes", 0),
        },
        "roofline": rl,
        "lower_compile_s": time.time() - t0,
    }
    if verbose:
        print(f"== {arch_id} x {shape_name} mesh={result['mesh']} fed={fed} "
              f"(window={cfg.sliding_window or 'full'})")
        print(f"   memory_analysis: arg={result['bytes_per_device']['argument']/1e9:.3f}GB "
              f"temp={result['bytes_per_device']['temp']/1e9:.3f}GB")
        print(f"   cost (scan-corrected): flops/chip={rl['hlo_flops_per_chip']:.3e} "
              f"bytes/chip={rl['hlo_bytes_per_chip']:.3e}")
        print(f"   collectives/chip: { {k: f'{v:.3e}' for k, v in rl['collectives'].items()} }")
        print(f"   roofline: compute={rl['compute_s']*1e3:.2f}ms "
              f"memory={rl['memory_s']*1e3:.2f}ms collective={rl['collective_s']*1e3:.2f}ms "
              f"-> dominant={rl['dominant']} useful_ratio={rl['useful_flops_ratio']:.3f}")
        print(f"   lower+compile(total): {result['lower_compile_s']:.1f}s", flush=True)
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fed", action="store_true", help="DFedRW gossip train step")
    ap.add_argument("--opt", action="store_true", help="beyond-paper optimized variant")
    ap.add_argument("--json", type=str, default="")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    results = []
    combos = (
        [(a, s) for a in ARCH_IDS for s in SHAPES]
        if args.all
        else [(args.arch, args.shape)]
    )
    ok = True
    for arch, shape in combos:
        try:
            results.append(dryrun_one(arch, shape, multi_pod=args.multi_pod,
                                      fed=args.fed, opt=args.opt))
        except Exception as e:  # noqa: BLE001 -- report every combo
            ok = False
            print(f"!! FAIL {arch} x {shape}: {type(e).__name__}: {e}", flush=True)
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results, f, indent=1)
    sys.exit(0 if ok else 1)


if __name__ == "__main__":
    main()
