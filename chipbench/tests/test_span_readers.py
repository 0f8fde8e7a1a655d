"""The readers of the round engine's dispatch and wait spans, on span lists
worked out by hand, and on runs whose engine records no such span."""
import pytest

from chipbench import run as R

SPANS = [("engine/plan", 1.0, 1.0005),
         ("engine/dispatch", 1.0005, 1.0025), ("engine/account", 1.0025, 1.003),
         ("engine/wait", 1.003, 1.483), ("engine/execute_round", 1.0005, 1.483),
         ("engine/plan", 2.0, 2.0005),
         ("engine/dispatch", 2.0005, 2.0045), ("engine/account", 2.0045, 2.005),
         ("engine/wait", 2.005, 2.495), ("engine/execute_round", 2.0005, 2.495)]


@pytest.mark.parametrize("name,value", [
    ("host_dispatch_ms.round", 3.0),          # spans of 2 and 4 ms
    ("host_wait_ms.round", 485.0),            # spans of 480 and 490 ms
])
def test_span_reader_by_hand(name, value):
    assert R.reader(name)({"host_spans": SPANS}) == pytest.approx(value)


@pytest.mark.parametrize("name", ["host_dispatch_ms.round", "host_wait_ms.round"])
@pytest.mark.parametrize("spans", [
    [],                                                       # no recorder attached
    [("engine/plan", 1.0, 1.0005), ("engine/execute_round", 1.0005, 1.483)],
], ids=["no_spans", "parent_span_only"])
def test_span_reader_finds_nothing(name, spans):
    assert R.reader(name)({"host_spans": spans}) is None
