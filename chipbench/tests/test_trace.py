"""The trace reduction on a small trace recorded on a v5e chip
(``record_trace.py --record``: three calls of a jitted 1024 x 1024 matmul
with a tanh, 2 ms host sleeps between them), with the expected numbers
worked out by hand from its events, and on synthetic events."""
import os

import pytest

from chipbench import trace as T

SMALL = os.path.join(os.path.dirname(__file__), "data", "small.xplane.pb")

# The trace's device ops, as (start, end) in ns on the device clock: per
# call a copy-start, a copy-done and the fused matmul.
OPS = [(935435043, 935435056), (935435057, 935435060), (935435061, 935448490),
       (938902699, 938902713), (938902714, 938902717), (938902718, 938916099),
       (942095115, 942095128), (942095130, 942095133), (942095135, 942108492)]
SLICE = (936524807, 946673217)              # host span chipbench/slice
CALLS = [(936531397, 937459577), (940203457, 940946707), (943414667, 943984567)]


@pytest.fixture(scope="module")
def small():
    return T.read(SMALL)


def test_reads_planes_lines_and_host_spans(small):
    assert list(small.devices) == [0]
    assert [(e.start, e.end) for e in small.devices[0]] == OPS
    assert len(small.modules[0]) == 3
    assert [(h.start, h.end) for h in small.host if h.name == "chipbench/call"] == CALLS
    assert T.span_window(small, "chipbench/slice") == SLICE


def test_clock_offset_puts_every_run_inside_its_call():
    tr = T.read(SMALL)
    off = T.align(tr)[0]
    # by hand: run k must start after call k starts and end before it ends;
    # call 3 allows [943414667 - 942095115, 943984567 - 942108492] =
    # [1.3196, 1.8761] ms and is the narrowest, so the middle is ~1.598 ms
    assert 1_319_552 <= off <= 1_876_075
    assert off == pytest.approx(1_598_000, abs=20_000)
    for run, (cs, ce) in zip(tr.modules[0], CALLS):
        assert cs <= run.start and run.end <= ce


def test_busy_idle_and_op_time_by_hand():
    tr = T.read(SMALL)
    T.align(tr)
    s = T.summarize(tr, T.span_window(tr, "chipbench/slice"))
    busy_ns = sum(e - b for b, e in OPS)      # no two ops overlap: 40216 ns
    assert busy_ns == 40216
    assert s["busy_s"] == pytest.approx(busy_ns * 1e-9, abs=1e-12)
    assert s["window_s"] == pytest.approx((SLICE[1] - SLICE[0]) * 1e-9)
    fused = sum(e - b for b, e in OPS[2::3]) * 1e-9
    top_name, top_s = s["device_ops"][0]
    assert "convolution_tanh_fusion" in top_name and top_s == pytest.approx(fused)
    # every idle ns is named: in the sleeps (slice) or in the calls
    assert sum(v for _, v in s["idle_gaps"]) == pytest.approx(s["window_s"] - s["busy_s"])
    assert {k for k, _ in s["idle_gaps"]} == {"chipbench/slice", "chipbench/call"}
    assert s["exposed_collective_s"] == 0.0


def test_union_clips_and_merges():
    assert T.union_ns([(0, 10), (5, 15), (20, 30)]) == 25
    assert T.union_ns([(0, 10), (5, 15), (20, 30)], lo=8, hi=25) == 12


def test_exposed_collective_time():
    ev = [T.Event("%collective-permute-start.1 = f32[4]", 0, 100),
          T.Event("%fusion.1 = f32[4]", 20, 50),
          T.Event("%fusion.2 = f32[4]", 40, 60),
          T.Event("%all-reduce.3 = f32[4]", 200, 230)]
    tr = T.Trace(devices={0: ev}, host=[T.Event("chipbench/slice", 0, 300)])
    s = T.summarize(tr, (0, 300))
    # permute 0-100 minus compute 20-60 = 60; all-reduce 30 alone
    assert s["exposed_collective_s"] == pytest.approx(90e-9)
    assert s["busy_s"] == pytest.approx(130e-9)


def test_gaps_named_by_innermost_host_span():
    ev = [T.Event("%a = f32[1]", 0, 10), T.Event("%b = f32[1]", 50, 60)]
    host = [T.Event("chipbench/slice", 0, 100), T.Event("chipbench/call", 0, 45),
            T.Event("engine/plan", 12, 30)]
    s = T.summarize(T.Trace(devices={0: ev}, host=host), (0, 100))
    gaps = dict(s["idle_gaps"])
    assert gaps == pytest.approx({"engine/plan": 40e-9, "chipbench/slice": 40e-9})


def test_op_time_is_self_time_of_nested_events():
    # a while loop (0-100) whose body ops (10-30, 40-70) lie inside it, and a
    # fusion after it; the window cuts the fusion at 120
    ev = [T.Event("%while.1 = (f32[4])", 0, 100), T.Event("%fusion.1 = f32[4]", 10, 30),
          T.Event("%fusion.2 = f32[4]", 40, 70), T.Event("%fusion.3 = f32[4]", 110, 150)]
    s = T.summarize(T.Trace(devices={0: ev}, host=[]), (0, 120))
    assert s["op_time_s"] == pytest.approx({"%while.1 = (f32[4])": 50e-9, "%fusion.1 = f32[4]": 20e-9,
                                            "%fusion.2 = f32[4]": 30e-9,
                                            "%fusion.3 = f32[4]": 10e-9})
    assert s["busy_s"] == pytest.approx(110e-9)
    assert s["device_ops"][0] == ["%while.1 = (f32[4])", pytest.approx(50e-9)]
