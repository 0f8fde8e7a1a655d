"""Each metric reader on a run record worked out by hand, and the readers
that find nothing to read return nothing."""
import pytest

from chipbench import run as R

PEAK = {"bf16_flops_per_s": 200e12, "hbm_bytes_per_s": 800e9}
KERNEL = ('%closed_call.4 = f32[1024,128]{1,0} custom-call(f32[1024,128]{1,0} %pad.1), '
          'custom_call_target="tpu_custom_call"')
XLA_CC = '%custom-call.3 = f32[8,16]{1,0} custom-call(), custom_call_target="AllocateBuffer"'

RUN = {
    "setup_s": 12.5, "window_s": 2.0, "calls": [0.4, 0.5, 0.5, 0.6], "work": 8000,
    "counts": {"model_flops_per_call": 1e12, "qdq_bytes_per_call": 4e9},
    "peak": PEAK, "chips": 1, "slice_calls": 2,
    "host_spans": [("engine/plan", 1.0, 1.002), ("engine/execute_round", 1.002, 1.5),
                   ("engine/plan", 2.0, 2.004)],
    "trace": {"window_s": 1.0, "busy_s": 0.75, "devices": {0: {}},
              "op_time_s": {KERNEL: 0.02, XLA_CC: 0.5, "%fusion.1 = f32[4]": 0.1}},
}


def _read(name, run=RUN):
    return R.reader(name)(run)


@pytest.mark.parametrize("name,value", [
    ("setup_s", 12.5),
    ("round_ms", 500.0),                      # 2 s over 4 rounds
    ("tokens_per_s", 4000.0),
    ("host_plan_ms.round", 3.0),              # spans of 2 and 4 ms
    ("mfu.round", 1e12 * 4 / 2.0 / 200e12 * 100),
    ("mfu.fedstep", 1e12 * 4 / 2.0 / 200e12 * 100),
    ("idle_share.round", 25.0),
    ("idle_share.fedstep", 25.0),
    # 2 calls x 4 GB at 800 GB/s is 10 ms, over 20 ms of kernel time
    ("qdq_roofline", 50.0),
])
def test_reader_by_hand(name, value):
    assert _read(name) == pytest.approx(value)


@pytest.mark.parametrize("name,change", [
    ("qdq_roofline", {"trace": dict(RUN["trace"], op_time_s={XLA_CC: 0.5})}),
    ("qdq_roofline", {"counts": {"model_flops_per_call": 1e12}}),
    ("qdq_roofline", {"trace": None}),
    ("idle_share.round", {"trace": None}),
    ("host_plan_ms.round", {"host_spans": []}),
    ("mfu.fedstep", {"counts": {}}),
])
def test_nothing_to_read(name, change):
    assert _read(name, dict(RUN, **change)) is None


def test_a_kind_without_a_file_reads_by_its_stem():
    assert R.reader("mfu.serve") is not None
    assert R.reader("mfu.serve")(RUN) == pytest.approx(_read("mfu.round"))
    with pytest.raises(FileNotFoundError):
        R.reader("no_such_metric.round")
