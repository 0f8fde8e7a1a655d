"""Benchmark harness entrypoint: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks/common.emit).

  PYTHONPATH=src python -m benchmarks.run                  # everything
  PYTHONPATH=src python -m benchmarks.run fig3 fig9        # subset
  REPRO_BENCH_ROUNDS=40 ... python -m benchmarks.run       # faster sweep
  REPRO_BENCH_SKIP_DRYRUN=1                                # skip pod-scale
"""
import os
import sys
import time
import traceback

MODULES = [
    "fig3_stat_heterogeneity",
    "fig5_dirichlet",
    "fig6_sys_heterogeneity",
    "fig8_topologies",
    "fig9_quant_bits",
    "fig10_epochs",
    "fig11_bound",
    "fig12_comm_cost",
    "fig13_language_model",
    "table4_latency",
    "prop1_quant_saving",
    "round_engine_bench",
    "serve_engine_bench",
    "sim_scenarios_bench",
    "pod_gossip_roofline",
]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def stamp_provenance() -> list[str]:
    """Stamp every shipped BENCH_*.json at the repo root with the shared
    provenance header (repro.obs.provenance): jax/numpy versions, platform,
    device kind, git rev, the report's own config hash, UTC timestamp.
    tools/docs_check.py enforces the header's presence. Returns the stamped
    paths."""
    import glob
    import json

    from repro.obs import provenance

    stamped = []
    for path in sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json"))):
        with open(path) as f:
            report = json.load(f)
        report["provenance"] = provenance(config=report.get("config"))
        with open(path, "w") as f:
            json.dump(report, f, indent=2)
            f.write("\n")
        stamped.append(os.path.basename(path))
    return stamped


def snapshot_bench() -> str | None:
    """Copy the committed BENCH_*.json aside before the sweep overwrites
    them, so the perf trajectory (old vs new numbers) can be diffed after."""
    import glob
    import shutil
    import tempfile

    paths = sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json")))
    if not paths:
        return None
    snap = tempfile.mkdtemp(prefix="bench_prev_")
    for p in paths:
        shutil.copy(p, snap)
    return snap


def diff_bench(snap: str | None) -> None:
    """Perf trajectory table: tools/obs_diff.py (--warn-only) of each
    refreshed BENCH_*.json against its pre-sweep snapshot. Report-only —
    a regression past threshold prints loudly but never fails the sweep;
    gating lives in the modules' own budgets."""
    import glob
    import shutil
    import subprocess

    if snap is None:
        return
    tool = os.path.join(ROOT, "tools", "obs_diff.py")
    try:
        for path in sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json"))):
            name = os.path.basename(path)
            prev = os.path.join(snap, name)
            if not os.path.exists(prev):
                print(f"# perf trajectory: {name} is new (no baseline)")
                continue
            print(f"# perf trajectory: {name} (old -> new)", flush=True)
            subprocess.run([sys.executable, tool, prev, path, "--warn-only",
                            "--top", "8"], check=False)
    finally:
        shutil.rmtree(snap, ignore_errors=True)


def main() -> None:
    sel = sys.argv[1:]
    picked = [m for m in MODULES if not sel or any(s in m for s in sel)]
    if os.environ.get("REPRO_BENCH_SKIP_DRYRUN"):
        picked = [m for m in picked if m != "pod_gossip_roofline"]
    failed = []
    snap = snapshot_bench()
    print("name,us_per_call,derived")
    for mod in picked:
        t0 = time.time()
        try:
            __import__(f"benchmarks.{mod}", fromlist=["run"]).run()
            print(f"# {mod} done in {time.time()-t0:.1f}s", flush=True)
        except Exception:
            failed.append(mod)
            traceback.print_exc()
    stamped = stamp_provenance()
    print(f"# provenance stamped into {stamped}")
    diff_bench(snap)
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
