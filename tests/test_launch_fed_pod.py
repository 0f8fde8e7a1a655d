"""launch/train.py ``pod --fed`` argument plumbing, end to end.

The fed pod deployment is the launcher surface the sim-to-metal harness
hands schedules to, so its CLI knobs must actually reach the gossip
configuration: ``--pods`` sizes the pod axis, ``--gossip-every`` the mix
cadence, ``--bits`` the payload quantizer, ``--topology`` the mixing graph.
Each test runs the real entry point in a subprocess on 8 virtual devices
and asserts the echoed configuration plus the convergence sentinel (the
inter-pod spread line proves the gossip mix actually executed)."""
import os
import re
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

_FED_POD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    from repro.launch.train import main
    main({argv!r})
""")


def _run_pod(argv: list) -> str:
    code = _FED_POD.format(src=SRC, argv=argv)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert r.returncode == 0, r.stdout[-2000:] + r.stderr[-2000:]
    return r.stdout


@pytest.mark.slow
def test_fed_pod_args_reach_gossip_config():
    out = _run_pod(["pod", "--arch", "yi-6b", "--smoke", "--fed",
                    "--pods", "4", "--gossip-every", "2", "--bits", "8",
                    "--topology", "expander", "--steps", "4"])
    assert ("fed pod mode: 4 pods x data=2 topology=expander "
            "every=2 bits=8 wire_bytes/pod=") in out
    m = re.search(r"done \(inter-pod param spread=([0-9.]+)\)", out)
    assert m, out[-2000:]
    assert out.count("step ") == 4


@pytest.mark.slow
def test_fed_pod_defaults_every_device_is_a_pod():
    out = _run_pod(["pod", "--arch", "yi-6b", "--smoke", "--fed",
                    "--steps", "2"])
    assert ("fed pod mode: 8 pods x data=1 topology=ring "
            "every=1 bits=32") in out
    assert "done (inter-pod param spread=" in out
