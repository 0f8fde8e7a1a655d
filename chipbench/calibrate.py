"""Readings that the limits of ``correct`` are set from, for one cell.

    python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,... --faults 1,2,3

For every seed in ``--seeds``: the cell's session is built as a run builds
it (set-up and the three checked calls through the program), and its
numbers are compared with the float32 reference: the lower readings. For
every seed in ``--faults`` also, in the program's place: the reference
computed in bfloat16 (the control), and the reference with half of every
batch left out and the mean taken over the rest. A step that returns its
state unchanged reads 1 by construction and needs no run. No measured
window: a training cell's numbers come from its first three calls. Each
seed prints one JSON line, its numbers with the raw readings they come
from; the last line gives, per number, the largest program reading and
the smallest control and fault readings.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), p)
                for p in ("src", "")]

from chipbench import run as R  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    cell = R.load_cell(args.workload)
    R.enable_cache()
    import jax
    import jax.numpy as jnp

    from chipbench.refs import compare

    R._devices(cell["chips"], True)
    driver = R._module(os.path.join(R.BENCH, "drivers", f"{cell['wl']['driver']}.py"))
    faults = {int(s) for s in args.faults.split(",") if s}
    control = dict(cell["cfg"]["control"])
    if "dtype" in control:
        control["dtype"] = jnp.dtype(control["dtype"])
    worst = {"program": {}, "control": {}, "half_batch": {}}
    for seed in [int(s) for s in args.seeds.split(",")]:
        session = driver.Session(cell["cfg"], cell["wl"], seed, cell["chips"])
        prog = session.readings
        session.release()
        gc.collect()
        ref = session.reference()
        line = {"seed": seed, "program": compare.numbers(prog, ref)}
        readings = {"program": prog, "reference": ref}
        if seed in faults:
            for name, kw in (("control", control), ("half_batch", {"batch_frac": 0.5})):
                readings[name] = dict(session.reference(**kw), first_name=prog["first_name"])
                line[name] = compare.numbers(readings[name], ref)
        for kind, nums in line.items():
            if kind == "seed":
                continue
            pick = max if kind == "program" else min
            for k, v in nums.items():
                worst[kind][k] = pick(worst[kind].get(k, v), v)
        line["readings"] = {kind: {k: v if k == "first_name" else np.asarray(v).tolist()
                                   for k, v in r.items()} for kind, r in readings.items()}
        print(json.dumps(line), flush=True)
        del session, ref
        gc.collect()
    print(json.dumps({"cell": args.workload, "lower": worst["program"],
                      "control_min": worst["control"], "half_batch_min": worst["half_batch"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
