"""chip_smoke.py's phases at smoke widths on the CPU (a rehearsal of the chip
run: paths, arguments and checks, not speed), plus its refusal to run
anywhere but on a TPU."""
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
sys.path.insert(0, ROOT)

import chip_smoke as C  # noqa: E402

# the fnn_mnist round at a tenth of the scale; the kernel is interpreted here
ROUND = C.RoundSmoke(n=10, m_chains=3, k_walk=3, hidden=(32,),
                     n_samples=1000, rounds=2, tol=1e-6)


def test_round_phase_smoke():
    out = C.run_round_phase(ROUND, seed=0)
    assert set(out["losses"]) == {32, 8}
    assert out["fp32_diff"] == 0.0          # the CPU is bit-exact
    assert not out["kernel"]                # interpret mode: no custom call


def test_pod_phase_smoke():
    out = C.run_pod_phase(C.PodSmoke(smoke=True, seq=32, steps=2), seed=0)
    assert [len(v) for v in out.values()] == [2, 2]
    # one pod: the gossip is the identity, so the wire width changes nothing
    assert out[32] == out[8]


def test_serve_phase_smoke():
    out = C.run_serve_phase(C.ServeSmoke(smoke=True, prompt_len=12, gen=6,
                                         chunk=4), seed=0)
    assert len(out["tokens"]) == 4


_GOSSIP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import sys
    sys.path.insert(0, {root!r})
    import chip_smoke as C
    out = C.run_gossip_phase(C.GossipSmoke(smoke=True, seq=32), seed=0)
    assert out[32]["mix_err"] <= 1e-5, out
    print("GOSSIP_PHASE_OK")
""")


@pytest.mark.slow
def test_gossip_phase_four_virtual_devices():
    r = subprocess.run([sys.executable, "-c", _GOSSIP.format(root=ROOT)],
                       capture_output=True, text=True, timeout=600)
    assert "GOSSIP_PHASE_OK" in r.stdout, r.stdout[-3000:] + r.stderr[-3000:]


def test_script_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env,
                       cwd=ROOT)
    assert r.returncode != 0
    assert "no TPU found" in r.stderr
    assert '"ok"' not in r.stdout
