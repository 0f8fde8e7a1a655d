"""Milliseconds per traced call in which the gossip's collectives run and
no other operation does, averaged over the chips (``chipbench/trace.py``'s
``exposed_collective_s``): the part of the exchange between pods that
compute does not hide. A trace with no collective op reads nothing."""
from chipbench.trace import COLLECTIVE


def read(run: dict):
    tr = run["trace"]
    if not tr or not run["slice_calls"] or not any(COLLECTIVE.search(k) for k in tr["op_time_s"]):
        return None
    return tr["exposed_collective_s"] / run["slice_calls"] * 1e3
