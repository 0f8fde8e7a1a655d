"""The MLA + routed-expert cell (DeepSeek-V2-Lite's layers): its FLOP
counts against hand counts, and at smoke size on the CPU the program
against the plain reference (``refs/mla_moe.py``), the bfloat16 control,
and whole runs with the step broken underneath."""
import copy

import jax.numpy as jnp
import pytest

from chipbench import mla_moe_flops as F
from chipbench import run as R
from chipbench.refs import compare
from chipbench.tests import tiny
from chipbench.tests.test_faults import _break_fedstep

CELL = "fedstep.deepseek-v2-lite-5l.1pod"
SMALL = {"hidden_size": 64, "num_attention_heads": 4, "qk_nope_head_dim": 16,
         "qk_rope_head_dim": 8, "v_head_dim": 16, "kv_lora_rank": 32, "intermediate_size": 128,
         "moe_intermediate_size": 32, "router_experts": 8, "n_routed_experts": 4,
         "expert_start": 2, "num_experts_per_tok": 3, "n_shared_experts": 1, "vocab_size": 256,
         "num_hidden_layers": 3,
         # the held experts in float32 too: on the CPU only summation order
         # differs from the reference (the cell's bfloat16 is the chip's)
         "expert_dtype": None}
SEED = 2**31 + 5


def _cell() -> dict:
    c = copy.deepcopy(R.load_cell(CELL))
    c["cfg"].update(SMALL)
    c["wl"]["traffic"].update(batch=2, seq=16)
    return c


def test_flops_per_token_hand_count():
    m = dict(R.load_cell(CELL)["cfg"], **SMALL)
    # MLA: wq 64x(4x24), w_dkv 64x(32+8), kv_b 32x(4x(16+16)), wo (4x16)x64
    mla = 64 * 96 + 64 * 40 + 32 * 128 + 64 * 64
    dense = 3 * 64 * 128                               # one leading dense layer
    routed = 3 * 4 / 8                                 # k * held / E experts a token
    moe = 64 * 8 + (1 + routed) * 3 * 64 * 32          # router, shared + routed
    matmul = 3 * mla + dense + 2 * moe + 64 * 256      # + the head
    attention = 6 * 3 * 10 * 4 * (24 + 16)             # layers, seq 10, heads, qk + v
    assert F.train_flops_per_token(m, seq=10) == 6 * matmul + attention
    # 18 x rows x d x width over the 2 MoE layers, rows = 7 tokens x 3 x 4 / 8
    assert F.experts_least_flops(m, tokens=7) == 18 * 10.5 * 64 * 32 * 2


def test_cell_step_counts():
    m = R.load_cell(CELL)["cfg"]
    assert 8192 * F.train_flops_per_token(m, 2048) == pytest.approx(15.26e12, rel=1e-3)
    assert F.experts_least_flops(m, 8192) == pytest.approx(1.2756e12, rel=1e-3)


@pytest.fixture(scope="module")
def readings():
    cell = _cell()
    driver = R._module(f"{R.BENCH}/drivers/{cell['wl']['driver']}.py")
    session = driver.Session(cell["cfg"], cell["wl"], SEED, 1)
    prog = session.readings
    session.release()
    ref = session.reference()
    ctrl = dict(session.reference(dtype=jnp.bfloat16), first_name=prog["first_name"])
    return compare.numbers(prog, ref), compare.numbers(ctrl, ref)


def test_program_matches_reference(readings):
    prog, _ = readings
    # float32 on the CPU: only summation order differs
    assert max(prog.values()) < 1e-4, prog


def test_lower_precision_fails(readings):
    prog, ctrl = readings
    assert any(ctrl[k] > 10 * max(prog[k], 1e-6) for k in prog), (prog, ctrl)
    limits = tiny.limits(CELL)
    assert any(ctrl[k] > limits[k] for k in limits), (ctrl, limits)


def test_sound_run_is_correct():
    res = R.execute(_cell(), SEED, 0.3, False, check_devices=False, peak=tiny.PEAK)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["unchanged", "half_batch"])
def test_broken_step_is_not_correct(monkeypatch, fault):
    _break_fedstep(monkeypatch, fault)
    res = R.execute(_cell(), SEED, 0.3, False, check_devices=False, peak=tiny.PEAK)
    assert not res["correct"], res["checks"]
