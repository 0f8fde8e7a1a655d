"""The chip benchmark: one run of one cell.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell is an entry of ``BENCHMARK.json``'s ``workloads``. Its file
``chipbench/workloads/<cell>.json`` names the configuration
(``chipbench/configs/<config>.json``), the driver
(``chipbench/drivers/<driver>.py``), the chips, the traffic parameters and
the limits of the compared numbers. A run:

1. turns on JAX's persistent compilation cache in ``.chipbench/jax_cache``
   inside the checkout, and refuses to run without a TPU or with fewer chips than the
   cell asks for;
2. builds the driver's session: data and weights from ``--seed`` on the
   device, and the first three calls through the window's own call, which
   compile every program and are the calls the reference replays;
3. measures for ``--seconds``, counting the compilations inside the window;
4. with ``--trace 1``, also profiles a short slice after the window and
   reduces its ``.xplane.pb`` (``chipbench/trace.py``);
5. reads the device's peak memory, frees the program's state, runs the
   plain reference (``chipbench/refs``) and compares;
6. prints the compared numbers beside their limits as the last lines of
   standard error, and one JSON line as the last line of standard output.

With ``--trace 0`` the JSON carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics; each metric is read by its own reader,
``chipbench/metrics/<metric>.py``, or, where a metric ``<stem>.<kind>`` has
no file of its own, by the reader of its stem, ``chipbench/metrics/<stem>.py``.
A traced run also prints the timed program's ``memory_analysis()`` beside
the allocator's peak.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "chipbench")
# the persistent compilation cache: inside the checkout, at a fixed path (the
# path is part of every entry's key), and the benchmark's own
CACHE = os.path.join(ROOT, ".chipbench", "jax_cache")
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

__all__ = ["load_cell", "reader", "enable_cache", "execute", "main"]


class NoChip(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def _json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def _module(path: str):
    name = "chipbench_" + os.path.basename(path)[:-3].replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(name: str, root: str = ROOT) -> dict:
    """The cell's workload, configuration and metric entries."""
    bench = _json(root, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    wl = _json(root, "chipbench", "workloads", f"{name}.json")
    if (wl["config"], wl["chips"]) != (entry["config"], entry["chips"]):
        raise ValueError(f"{name}: workload file and BENCHMARK.json disagree")
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads", [name])]
    moved = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if (name in m["workloads"] if "workloads" in m else m["moves"] in moved)]
    return {"name": name, "wl": wl, "chips": wl["chips"],
            "cfg": _json(root, "chipbench", "configs", f"{wl['config']}.json"),
            "end_to_end": e2e, "per_layer": per_layer}


def reader(name: str, root: str = ROOT):
    """The ``read`` function of a metric: its own file, else its stem's."""
    own = os.path.join(root, "chipbench", "metrics", f"{name}.py")
    stem = os.path.join(root, "chipbench", "metrics", f"{name.split('.')[0]}.py")
    return _module(own if os.path.exists(own) else stem).read


class _Compiles:
    """Counts JAX's tracing, compiling and cache-loading events."""

    EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
              "/jax/core/compile/backend_compile_duration": "compiles",
              "/jax/compilation_cache/cache_retrieval_time_sec": "cache_loads"}

    def __init__(self):
        self.total = dict.fromkeys(self.EVENTS.values(), 0)
        self.mark = dict(self.total)

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.total[self.EVENTS[event]] += 1

    def since_mark(self) -> dict:
        return {k: self.total[k] - self.mark[k] for k in self.total}


def enable_cache() -> None:
    """Point JAX's persistent compilation cache at ``CACHE``, whatever the
    environment says, and cache every program."""
    os.makedirs(CACHE, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", CACHE)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def _devices(chips: int, check: bool):
    import jax

    devs = jax.devices()
    if check and devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform is {devs[0].platform!r})")
    if check and len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


def _profile(session, logdir: str, min_s: float = 1.0, min_calls: int = 3):
    """Trace a short steady slice of calls; return its reduction."""
    import jax
    from jax.profiler import TraceAnnotation

    from chipbench import trace as T

    shutil.rmtree(logdir, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(logdir, profiler_options=opts)
    try:
        with TraceAnnotation("chipbench/clock"):
            t_clock = time.perf_counter()
        n, t0 = 0, time.perf_counter()
        with TraceAnnotation("chipbench/slice"):
            while n < min_calls or time.perf_counter() - t0 < min_s:
                with TraceAnnotation("chipbench/call"):
                    session.call()
                n += 1
    finally:
        jax.profiler.stop_trace()
    tr = T.read(T.find_xplane(logdir))
    offsets = T.align(tr)
    clock = next(h for h in tr.host if h.name == "chipbench/clock")
    offset = clock.start - t_clock * 1e9
    tr.host.extend(T.Event(name, a * 1e9 + offset, b * 1e9 + offset)
                   for name, a, b in session.host_spans() if b >= t_clock)
    tr.host.sort(key=lambda e: e.start)
    return dict(T.summarize(tr, T.span_window(tr, "chipbench/slice")), offsets=offsets), n


def _short(op: str) -> str:
    """An XLA op's trace name is its HLO text; keep the instruction's name
    and the shape it makes."""
    name, _, rest = op.partition(" = ")
    if not rest:
        return op[:80]
    shape = "" if rest.startswith("(") else " " + rest.split(" ", 1)[0].split("{")[0]
    return name.lstrip("%") + shape


def execute(cell: dict, seed: int, seconds: float, trace: bool, *,
            check_devices: bool = True, peak: dict | None = None,
            trace_dir: str | None = None) -> dict:
    """One run; returns the result line as a dict."""
    import jax

    from chipbench.refs import compare

    count = _Compiles()
    jax.monitoring.register_event_duration_secs_listener(count)
    devs = _devices(cell["chips"], check_devices)
    kind = devs[0].device_kind
    if peak is None:
        peaks = _json(BENCH, "peaks.json")["devices"]
        if kind not in peaks:
            raise KeyError(f"no peak for device kind {kind!r} in chipbench/peaks.json")
        peak = peaks[kind]
    driver = _module(os.path.join(BENCH, "drivers", f"{cell['wl']['driver']}.py"))
    session = driver.Session(cell["cfg"], cell["wl"], seed, cell["chips"])
    setup_s = time.perf_counter() - T_START
    if trace:
        session.attach_recorder()
    count.mark = dict(count.total)
    print(f"set-up: {setup_s!r} s; compiles={count.total['compiles']} "
          f"cache_loads={count.total['cache_loads']} traces={count.total['traces']}", flush=True)
    calls, work = [], 0
    t0 = t1 = time.perf_counter()
    while t1 - t0 < seconds:
        c0 = time.perf_counter()
        work += session.call()
        t1 = time.perf_counter()
        calls.append(t1 - c0)
    window_s = t1 - t0
    inside = count.since_mark()
    print(f"window: {len(calls)} calls in {window_s!r} s; inside the window "
          f"compiles={inside['compiles']} cache_loads={inside['cache_loads']} "
          f"traces={inside['traces']}", flush=True)
    summary, slice_calls = None, 0
    if trace:
        summary, slice_calls = _profile(
            session, trace_dir or os.path.join(ROOT, ".chipbench", "trace", cell["name"]))
        kernels = {_short(k): v for k, v in summary["op_time_s"].items()
                   if 'custom_call_target="tpu_custom_call"' in k}
        print(f"trace: {slice_calls} calls, busy_s={summary['busy_s']!r} of "
              f"{summary['window_s']!r} s, device clock offsets {summary['offsets']} ns, "
              f"Pallas kernels {kernels}", flush=True)
    stats = [d.memory_stats() or {} for d in devs]
    mem = max(s.get("peak_bytes_in_use", 0) for s in stats)
    memory = None
    if trace:
        # the timed program's own account, beside the allocator's peak:
        # arguments + outputs - aliased (donated) + temporaries
        analysis = session.memory()
        memory = {k: int(getattr(analysis, f"{k}_size_in_bytes"))
                  for k in ("argument", "output", "alias", "temp", "generated_code")}
        memory["footprint"] = (memory["argument"] + memory["output"] - memory["alias"]
                               + memory["temp"])
        print(f"memory: peak_bytes_in_use={mem} memory_analysis bytes {memory}", flush=True)
    run = {"setup_s": setup_s, "window_s": window_s, "calls": calls, "work": work,
           "counts": session.counts, "peak": peak, "chips": cell["chips"],
           "trace": summary, "slice_calls": slice_calls, "host_spans": session.host_spans()}
    metrics = {}
    for m in cell["per_layer"] if trace else cell["end_to_end"]:
        val = reader(m["name"])(run)
        if val is not None and math.isfinite(val):
            metrics[m["name"]] = {"value": float(val), "unit": m["unit"]}
    readings = session.readings
    session.release()
    gc.collect()
    t_ref = time.perf_counter()
    ref = session.reference()
    print(f"reference: {time.perf_counter() - t_ref!r} s", flush=True)
    numbers = compare.numbers(readings, ref)
    limits = cell["wl"].get("limits") or {}
    # an infinite gap (a non-finite or missing reading) prints as the largest float
    checks = {k: {"value": min(v, sys.float_info.max), "limit": limits.get(k)}
              for k, v in numbers.items()}
    correct = all(c["limit"] is not None and c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": mem}
    result = {"correct": correct, "attempted": len(calls), "failed": int(session.failed),
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"], device["window_s"] = summary["busy_s"], summary["window_s"]
        result["breakdown"] = {"device_ops": [[_short(k), v] for k, v in summary["device_ops"]],
                               "idle_gaps": summary["idle_gaps"]}
        result["memory_analysis"] = memory
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    enable_cache()
    try:
        result = execute(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    d = result["device"]
    print(f"device: {d['platform']} {d['kind']} x{d['count']}; "
          f"memory_peak_bytes={d['memory_peak_bytes']}", flush=True)
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
