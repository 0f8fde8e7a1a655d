"""A run with the timed path broken underneath comes out not correct.

Each test drives a whole run of a committed cell at smoke size on the CPU
(``run.execute`` without its look for a chip), with the program's step
patched to fail in one of the ways a training cell can: a step that returns
its state unchanged, and a step that leaves out half of the batch and takes
the mean over the rest. The sound run of the same cell is correct under the
committed limits."""
import jax.numpy as jnp
import pytest

from chipbench import run as R
from chipbench.tests import tiny

ROUNDS = ["round.fnn2-mnist.h90", "round.lstm-reddit.q8"]
SEED = 2**31 + 11


def _run(name):
    return R.execute(tiny.cell(name), SEED, 0.3, False, check_devices=False, peak=tiny.PEAK)


def _break_round(monkeypatch, fault):
    from repro.core import dfedrw

    build = dfedrw.DFedRW._build_round_fn_flat

    def broken(self, bits):
        fn = build(self, bits)

        def round_fn(device_flat, walk_devices, walk_mask, batch_idx, *rest):
            if fault == "unchanged":
                _, loss, gamma = fn(jnp.array(device_flat, copy=True), walk_devices,
                                    walk_mask, batch_idx, *rest)
                return device_flat, loss, gamma
            half = batch_idx[:, :, : batch_idx.shape[2] // 2]
            return fn(device_flat, walk_devices, walk_mask, half, *rest)

        return round_fn

    monkeypatch.setattr(dfedrw.DFedRW, "_build_round_fn_flat", broken)


def _break_fedstep(monkeypatch, fault):
    from repro.dist import steps

    make = steps.make_fed_train_step

    def broken(*a, **k):
        fn, specs, abstract = make(*a, **k)

        def step_fn(params, vel, batch, step, key):
            if fault == "unchanged":
                return params, vel, fn(params, vel, batch, step, key)[2]
            half = {name: v[:, : v.shape[1] // 2] for name, v in batch.items()}
            return fn(params, vel, half, step, key)

        return step_fn, specs, abstract

    monkeypatch.setattr(steps, "make_fed_train_step", broken)


@pytest.mark.parametrize("name", ROUNDS + ["fedstep.yi-6b-2l.1pod"])
def test_sound_run_is_correct(name):
    res = _run(name)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
@pytest.mark.parametrize("name", ROUNDS + ["fedstep.yi-6b-2l.1pod"])
def test_broken_step_is_not_correct(monkeypatch, name, fault):
    (_break_round if name.startswith("round.") else _break_fedstep)(monkeypatch, fault)
    res = _run(name)
    assert not res["correct"], res["checks"]
