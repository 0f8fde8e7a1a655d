"""Record the small trace that ``test_trace.py`` reads, or describe a trace.

    python3 chipbench/tests/record_trace.py --record <dir>    # on a TPU
    python3 chipbench/tests/record_trace.py --describe <dir>

``--record`` profiles three calls of a small jitted matmul with host sleeps
between them, each call inside a ``chipbench/call`` annotation and all of
them inside ``chipbench/slice``, and then describes the trace.
``--describe`` prints every plane and line of the newest ``.xplane.pb``
under the directory, with event counts and a few event names and stats.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


def record(logdir: str) -> None:
    import jax
    import jax.numpy as jnp
    from jax.profiler import TraceAnnotation

    f = jax.jit(lambda a: jnp.tanh(a @ a))
    x = jnp.ones((1024, 1024), jnp.float32)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(logdir, profiler_options=opts)
    with TraceAnnotation("chipbench/slice"):
        for _ in range(3):
            with TraceAnnotation("chipbench/call"):
                f(x).block_until_ready()
            time.sleep(0.002)
    jax.profiler.stop_trace()


def describe(logdir: str) -> None:
    from jax.profiler import ProfileData

    from chipbench import trace as T

    path = T.find_xplane(logdir)
    print(f"{path}: {os.path.getsize(path)} bytes")
    for plane in ProfileData.from_file(path).planes:
        print(f"PLANE {plane.name!r} stats={list(plane.stats)[:6]}")
        for line in plane.lines:
            evs = list(line.events)
            names = list(dict.fromkeys(e.name for e in evs))[:6]
            print(f"  LINE {line.name!r} events={len(evs)} names={names}")
            for e in evs[:2]:
                print(f"    {e.name!r} start={e.start_ns} dur={e.duration_ns} "
                      f"stats={[(k, str(v)[:120]) for k, v in e.stats][:8]}")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--record")
    ap.add_argument("--describe")
    args = ap.parse_args()
    if args.record:
        record(args.record)
    describe(args.record or args.describe)
