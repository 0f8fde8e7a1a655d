"""Serving launcher: continuous-batching engine over the sharded KV-cache
path (repro.serve). Generates a synthetic request workload, runs it through
`ServeEngine`, and reports per-request TTFT/TPOT plus engine throughput.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --smoke \
      --requests 32 --max-concurrency 8

  # staggered Poisson arrivals, mixed lengths, 8 virtual devices:
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-72b --smoke \
      --requests 32 --max-concurrency 8 --arrival 0.5 --mixed \
      --mesh-model 2 --verify
"""
from __future__ import annotations

import argparse
import json


def build_requests(args, cfg):
    """Synthetic workload: fixed lengths by default; --mixed draws prompt
    lengths U[plen/2, plen] and budgets U[gen/4, gen]; --arrival r spreads
    arrivals as Poisson(rate=r requests per engine step). enc-dec archs get
    random frontend embeddings per request."""
    import numpy as np

    from repro.serve import Request

    rng = np.random.default_rng(args.seed)
    step = 0
    reqs = []
    for i in range(args.requests):
        plen = int(rng.integers(max(args.prompt_len // 2, 1), args.prompt_len + 1)) \
            if args.mixed else args.prompt_len
        gen = int(rng.integers(max(args.gen // 4, 1), args.gen + 1)) \
            if args.mixed else args.gen
        if args.arrival > 0 and i > 0:
            step += int(rng.poisson(1.0 / args.arrival))
        embeds = None
        if cfg.enc_dec:
            embeds = rng.normal(
                size=(cfg.frontend_tokens, cfg.d_model)).astype(np.float32)
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, size=(plen,)),
            max_tokens=gen, eos_id=args.eos_id, temperature=args.temperature,
            arrival_step=step, embeds=embeds))
    return reqs


def sequential_reference(cfg, params, req, max_len: int, step=None):
    """The pre-engine serving semantics: one request, token-at-a-time
    prefill through the decode path, then greedy/temp-0 decode. The
    engine's per-request outputs must match this bit-for-bit at temp 0.
    ``step`` is the (shared, pre-compiled) jitted decode program — jit
    caches key on the function object, so it must be built ONCE by the
    caller, not per request."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    cache = T.init_cache(cfg, 1, max_len, jnp.float32,
                         enc_len=cfg.frontend_tokens if cfg.enc_dec else 0)
    if cfg.enc_dec:
        from repro.models.transformer import _run_encoder
        cache["enc_out"] = _run_encoder(
            cfg, params, jnp.asarray(req.embeds)[None], remat=False)
    if step is None:
        step = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t))
    logits = None
    for t in range(len(req.prompt)):
        logits, cache = step(params, cache, jnp.asarray(req.prompt[None, t:t + 1]))
    out = []
    for _ in range(req.max_tokens):
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        if req.eos_id >= 0 and tok == req.eos_id:
            break
        logits, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32))
    return out


def verify_sequential(cfg, params, results, max_len: int) -> list[int]:
    """``--verify``: replay every finished request through the sequential
    decode path; return the ids whose tokens differ (temp-0 engines must
    return none)."""
    import jax

    from repro.models import transformer as T

    step = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t))
    return [st.request.rid for st in results
            if st.generated != sequential_reference(cfg, params, st.request,
                                                    max_len, step)]


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--arch", required=True)
    # --smoke was action="store_true", default=True — impossible to disable.
    # It stays accepted for compat; --full is the actual override.
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="use the reduced smoke config (default; see --full)")
    ap.add_argument("--full", action="store_true",
                    help="use the full-size architecture config")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-concurrency", type=int, default=8,
                    help="engine cache slots (max in-flight requests)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32, help="max new tokens per request")
    ap.add_argument("--chunk", type=int, default=16, help="prefill chunk size")
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-slot cache capacity (0 = prompt+gen)")
    ap.add_argument("--arrival", type=float, default=0.0,
                    help="mean arrivals per engine step (0 = all at step 0)")
    ap.add_argument("--mixed", action="store_true",
                    help="draw mixed prompt/gen lengths instead of fixed")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=-1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--mesh-model", type=int, default=1,
                    help="model-axis size; data axis gets the rest of the devices")
    ap.add_argument("--verify", action="store_true",
                    help="replay each request through the sequential decode "
                         "path and require identical outputs (temp 0)")
    ap.add_argument("--json", default="", help="write the metrics summary here")
    ap.add_argument("--obs", default="",
                    help="record a repro.obs telemetry stream (JSONL) here "
                         "(report: python tools/obs_report.py <path>)")
    ap.add_argument("--trace", action="store_true",
                    help="with --obs: record per-request causal span trees "
                         "(admit/prefill_chunk/decode tspan events; export: "
                         "python tools/obs_trace_export.py <obs.jsonl>)")
    return ap.parse_args(argv)


def main(argv=None) -> None:
    args = parse_args(argv)

    import jax
    import jax.numpy as jnp

    from repro.configs import get_arch, get_smoke
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_host_mesh
    from repro.models import transformer as T
    from repro.serve import EngineConfig, ServeEngine

    enable_compile_cache()
    smoke = not args.full
    cfg = get_smoke(args.arch) if smoke else get_arch(args.arch)
    dtype = jnp.float32 if smoke else jnp.bfloat16
    params = T.init_params(cfg, jax.random.PRNGKey(0), dtype)
    max_len = args.max_len or (args.prompt_len + args.gen)
    n_dev = len(jax.devices())
    mesh = make_host_mesh(data=max(n_dev // args.mesh_model, 1), model=args.mesh_model)

    if args.trace and not args.obs:
        raise SystemExit("--trace requires --obs (it augments the obs "
                         "stream with tspan events)")
    obs = None
    if args.obs:
        from repro.obs import PausableWallClock, Recorder
        obs = Recorder(clock=PausableWallClock(), trace=args.trace)

    reqs = build_requests(args, cfg)
    eng = ServeEngine(cfg, params, EngineConfig(
        max_concurrency=args.max_concurrency, max_len=max_len,
        chunk=args.chunk, dtype=dtype, seed=args.seed), mesh=mesh, obs=obs)
    results = eng.run(reqs)

    summary = eng.metrics.summary()
    print(f"arch={cfg.name} devices={n_dev} mesh={dict(mesh.shape)} "
          f"slots={args.max_concurrency} chunk={eng.chunk} requests={len(reqs)}")
    for st in results:
        m = eng.metrics.requests[st.request.rid]
        print(f"  req {st.request.rid:3d}: prompt={m.prompt_len:3d} "
              f"gen={m.n_generated:3d} stop={st.stop:<10s} "
              f"ttft={m.ttft_s*1e3:7.1f}ms tpot={m.tpot_s*1e3:6.1f}ms "
              f"tokens={st.generated[:8]}{'...' if len(st.generated) > 8 else ''}")
    print(f"throughput: {summary['tok_s']:.1f} gen tok/s "
          f"({summary['total_tok_s']:.1f} incl. prefill) | "
          f"mean TTFT {summary['mean_ttft_s']*1e3:.1f}ms | "
          f"mean TPOT {summary['mean_tpot_s']*1e3:.1f}ms | "
          f"{summary['prefill_chunks']} prefill chunks + "
          f"{summary['decode_steps']} decode steps "
          f"(traces: {eng.trace_counts})")

    if args.verify:
        if args.temperature > 0:
            raise SystemExit("--verify requires --temperature 0")
        bad = verify_sequential(cfg, params, results, max_len)
        if bad:
            raise SystemExit(f"VERIFY FAILED: engine != sequential decode for rids {bad}")
        print(f"verify: all {len(results)} requests bit-identical to the "
              f"sequential decode path")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(summary, f, indent=2)
        print(f"wrote {args.json}")

    if args.obs:
        from repro.obs import provenance
        obs.save(args.obs, provenance=provenance(config=vars(args)),
                 workload="serve", arch=cfg.name)
        print(f"obs: wrote {args.obs} "
              f"(report: python tools/obs_report.py {args.obs})")


if __name__ == "__main__":
    main()
