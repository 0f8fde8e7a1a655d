"""Dry-run machinery tests.

1. The scan-correction identity (corrected_costs) is validated against a
   fully-unrolled lower of the same model: flops/bytes must agree closely.
2. The dryrun CLI end-to-end for one cheap (arch x shape) on the production
   16x16 mesh (proves deliverable (e) wiring).

Both run in subprocesses: the 512-placeholder XLA flag must not leak here.
"""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

import pytest

# No module-level importorskip: repro.dist.sharding/steps have landed, and a
# broken import inside repro.launch.dryrun must surface as the real failing
# import at collection, not as a silent skip. (Tests that need pieces which
# have not landed yet guard themselves function-locally.)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_CORRECTION = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import sys
    sys.path.insert(0, {src!r})
    import jax.numpy as jnp
    import jax
    from repro.launch import dryrun as D
    from repro.models.config import ArchConfig

    D.SHAPES["tiny_train"] = dict(kind="train", seq_len=128, global_batch=16)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 4), ("data", "model"))
    cfg = ArchConfig(name="tiny", n_layers=6, d_model=128, n_heads=4,
                     n_kv_heads=2, d_ff=256, vocab=512)

    corrected = D.corrected_costs(cfg, "tiny_train", mesh, fed=False)
    full = D._raw_costs(D._lower_combo(cfg, "tiny_train", mesh, fed=False, unroll=True))
    rel_f = abs(corrected["flops"] - full["flops"]) / full["flops"]
    rel_b = abs(corrected["bytes"] - full["bytes"]) / full["bytes"]
    print("REL", rel_f, rel_b)
    # Unrolled bodies CSE/fuse slightly differently; ~6-8% agreement measured.
    assert rel_f < 0.10, ("flops", corrected["flops"], full["flops"])
    assert rel_b < 0.25, ("bytes", corrected["bytes"], full["bytes"])
    print("CORRECTION_OK")
""")


@pytest.mark.slow
def test_scan_correction_matches_full_unroll():
    code = _CORRECTION.format(src=SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=900)
    assert "CORRECTION_OK" in r.stdout, r.stdout[-3000:] + r.stderr[-3000:]


@pytest.mark.slow
def test_dryrun_cli_single_combo():
    with tempfile.TemporaryDirectory() as td:
        out = os.path.join(td, "out.json")
        env = dict(os.environ, PYTHONPATH=SRC)
        r = subprocess.run(
            [sys.executable, "-m", "repro.launch.dryrun", "--arch", "mamba2-130m",
             "--shape", "decode_32k", "--json", out],
            capture_output=True, text=True, env=env, timeout=900,
        )
        assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
        res = json.load(open(out))[0]
        assert res["mesh"] == "16x16"
        rl = res["roofline"]
        assert rl["hlo_flops_per_chip"] > 0
        assert rl["dominant"] in ("compute", "memory", "collective")
        assert set(rl["collectives"]) <= {
            "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
            "collective-permute",
        }


def test_collective_bytes_parser():
    from repro.launch.dryrun import collective_bytes

    hlo = """
  %ag = bf16[8,128]{1,0} all-gather(bf16[1,128]{1,0} %x), replica_groups={}
  %ar = f32[256]{0} all-reduce(f32[256]{0} %y), to_apply=%sum
  ROOT %cp = (s8[64]{0}, u8[64]{0}) collective-permute(s8[64]{0} %z, u8[64]{0} %w)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 8 * 128 * 2
    assert out["all-reduce"] == 256 * 4
    assert out["collective-permute"] == 64 + 64
    assert out["total"] == out["all-gather"] + out["all-reduce"] + out["collective-permute"]


def test_input_specs_shapes():
    from repro.configs import get_arch
    from repro.launch.dryrun import SHAPES, input_specs, resolve_cfg

    cfg = get_arch("yi-6b")
    b = input_specs(cfg, "train_4k")
    assert b["tokens"].shape == (256, 4096)
    d = input_specs(cfg, "decode_32k")
    assert d["token"].shape == (128, 1)
    assert d["cache"]["slots"]["slot0"]["k"].shape[0] == cfg.n_layers

    # long_500k policy: dense archs get the sliding-window variant
    cfg_500k = resolve_cfg("yi-6b", "long_500k")
    assert cfg_500k.sliding_window == 8192
    dd = input_specs(cfg_500k, "long_500k")
    assert dd["cache"]["slots"]["slot0"]["k"].shape[2] == 8192  # ring buffer
    # SSM/hybrid run natively
    assert resolve_cfg("mamba2-130m", "long_500k").sliding_window == 0
    assert resolve_cfg("jamba-1.5-large-398b", "long_500k").sliding_window == 0


def test_model_flops_estimate():
    from repro.configs import get_arch
    from repro.launch.dryrun import model_flops_estimate

    cfg = get_arch("yi-6b")
    f = model_flops_estimate(cfg, "train_4k")
    assert abs(f - 6 * cfg.param_count() * 256 * 4096) / f < 1e-6
    moe = get_arch("grok-1-314b")
    f_moe = model_flops_estimate(moe, "train_4k")
    assert f_moe < 6 * moe.param_count() * 256 * 4096  # active < total
