"""The plain references against the program at smoke sizes on the CPU, and
the control: the reference in bfloat16 (the precision below float32) must
fail the comparison where the program passes."""
import jax.numpy as jnp
import pytest

from chipbench import run as R
from chipbench.refs import compare
from chipbench.tests import tiny

CELLS = ["round.fnn2-mnist.h90", "round.lstm-reddit.q8", "fedstep.yi-6b-2l.1pod"]


@pytest.fixture(scope="module", params=CELLS)
def readings(request):
    cell = tiny.cell(request.param)
    driver = R._module(f"{R.BENCH}/drivers/{cell['wl']['driver']}.py")
    session = driver.Session(cell["cfg"], cell["wl"], 2**31 + 5, 1)
    prog = session.readings
    memory = session.memory()
    session.release()
    ref = session.reference()
    ctrl = dict(session.reference(dtype=jnp.bfloat16), first_name=prog["first_name"])
    return request.param, compare.numbers(prog, ref), compare.numbers(ctrl, ref), memory


def test_program_matches_reference(readings):
    _, prog, _, _ = readings
    # float32 on the CPU: only summation order differs
    assert max(prog.values()) < 1e-4, prog


def test_lower_precision_fails(readings):
    name, prog, ctrl, _ = readings
    assert any(ctrl[k] > 10 * max(prog[k], 1e-6) for k in prog), (prog, ctrl)
    limits = tiny.limits(name)
    assert any(ctrl[k] > limits[k] for k in limits), (ctrl, limits)


def test_memory_analysis_of_the_timed_program(readings):
    *_, memory = readings
    assert memory.argument_size_in_bytes > 0 and memory.output_size_in_bytes > 0


@pytest.mark.parametrize("prog,ref,gap", [
    ([10.0, 8.0], [10.01, 8.0], 0.01 / 10.01),      # relative above 1 nat
    ([0.002, 0.5], [0.001, 0.5], 0.001),            # in nats below it
    ([float("nan"), 0.5], [0.001, 0.5], float("inf")),
])
def test_loss_gap(prog, ref, gap):
    assert compare.loss_gap(prog, ref) == pytest.approx(gap)
