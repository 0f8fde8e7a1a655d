"""``experts_roofline`` and ``gossip_exposed_ms`` on a trace reduced by
``chipbench/trace.py``, with the numbers worked out by hand, and on runs
with nothing to read."""
import pytest

from chipbench import run as R
from chipbench import trace as T

PEAK = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
GMM = ('%gmm.3 = f32[49152,1408]{1,0:T(8,128)} custom-call(bf16[49152,2048]{1,0} %a, '
       'bf16[8,2048,1408]{2,1,0} %b), custom_call_target="tpu_custom_call"')
TGMM = ('%tgmm.1 = f32[8,2048,1408]{2,1,0} custom-call(bf16[2048,49152]{1,0} %c, '
        'f32[49152,1408]{1,0} %d), custom_call_target="tpu_custom_call"')
QDQ = '%qdq.1 = f32[1024,128]{1,0} custom-call(f32[1024,128]{1,0} %p), custom_call_target="tpu_custom_call"'
PERMUTE = '%collective-permute-start.1 = (f32[4096]{0}, f32[4096]{0}) collective-permute-start(f32[4096]{0} %x)'
FUSION = "%fusion.7 = f32[4096]{0} fusion(f32[4096]{0} %y), kind=kLoop"


def _run(events: dict, slice_calls=2, chips=1, counts=None) -> dict:
    """A run record whose trace is the reduction of ``events`` (device ->
    (name, start ns, end ns)) over the window [0, 10 ms]."""
    tr = T.Trace(devices={d: [T.Event(*e) for e in evs] for d, evs in events.items()}, host=[])
    return {"trace": T.summarize(tr, (0.0, 10e6)), "slice_calls": slice_calls, "chips": chips,
            "counts": counts or {}, "peak": PEAK}


EVENTS = {0: [(GMM, 0.0, 2e6), (TGMM, 2e6, 3e6), (QDQ, 3e6, 4e6),
              (PERMUTE, 5e6, 7e6), (FUSION, 6e6, 6.5e6)],
          1: [(PERMUTE, 5e6, 8e6)]}


def test_experts_roofline_by_hand():
    # 2 calls x 0.3 TFLOP over 200 TFLOP/s is 3 ms, over 3 ms of gmm + tgmm
    run = _run(EVENTS, counts={"experts_flops_per_call": 0.3e12})
    # the op time is summed over both devices and averaged: 3 ms / 2
    assert R.reader("experts_roofline")(run) == pytest.approx(3.0 / 1.5 * 100)
    one = _run({0: EVENTS[0]}, counts={"experts_flops_per_call": 0.3e12})
    assert R.reader("experts_roofline")(one) == pytest.approx(100.0)


def test_gossip_exposed_ms_by_hand():
    # device 0: the permute's 2 ms less the fusion's 0.5 ms; device 1: 3 ms;
    # mean 2.25 ms over 2 calls
    assert R.reader("gossip_exposed_ms")(_run(EVENTS)) == pytest.approx(1.125)


@pytest.mark.parametrize("name,run", [
    ("experts_roofline", _run({0: [(QDQ, 0.0, 1e6)]}, counts={"experts_flops_per_call": 1e12})),
    ("experts_roofline", _run(EVENTS)),                       # no count: not this model
    ("gossip_exposed_ms", _run({0: [(GMM, 0.0, 1e6), (FUSION, 1e6, 2e6)]})),   # one pod
    ("gossip_exposed_ms", dict(_run(EVENTS), trace=None)),    # an untraced run
])
def test_nothing_to_read(name, run):
    assert R.reader(name)(run) is None
