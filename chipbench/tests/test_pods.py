"""The four-pod cell's reference (``refs/fedstep_pods_ref.py``) on four
virtual CPU devices: its ring mixing against the dense mixing matrix at
32 bits, and whole runs of the cell at smoke size, sound and with the
step broken underneath (the gossip left out among the faults)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

SCRIPT = textwrap.dedent("""
    import os, sys, copy, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    sys.path[:0] = [{root!r} + "/src", {root!r}]
    import jax, jax.numpy as jnp, numpy as np
    from chipbench import run as R
    from chipbench.refs import fedstep_pods_ref as P
    from chipbench.tests import tiny

    out = {{}}
    devs = jax.devices()[:4]
    pods = jax.sharding.NamedSharding(jax.sharding.Mesh(np.array(devs), ("pod",)),
                                      jax.sharding.PartitionSpec("pod"))
    rng = np.random.default_rng(0)
    trees = {{"a": rng.normal(size=(4, 3, 5)).astype(np.float32),
              "b": rng.normal(size=(4, 7)).astype(np.float32)}}
    mixed = P.mix(jax.device_put(trees, pods), jax.random.PRNGKey(0), 32, pods)
    w = np.zeros((4, 4))
    for i in range(4):
        for j in (i, (i + 1) % 4, (i - 1) % 4):
            w[i, j] = 1 / 3
    gaps = []
    for name in ("a", "b"):
        dense = np.einsum("ij,j...->i...", w, trees[name])
        gaps.append(float(np.max(np.abs(np.asarray(mixed[name]) - dense))))
        assert mixed[name].addressable_shards[1].device == devs[1]
    out["dense_gap"] = max(gaps)
    q = P.mix(jax.device_put(trees, pods), jax.random.PRNGKey(0), 8, pods)
    out["q8_gap"] = float(np.max(np.abs(np.asarray(q["a"]) - np.asarray(mixed["a"]))))

    def cell():
        # the four-pod cell at smoke size, with limits for the CPU's
        # float32: the 8-bit wire's
        # rounding is replayed, but a draw that lands within float32 rounding
        # of a grid step's edge rounds the other way, so the change after
        # three steps gets room for a few such steps
        c = copy.deepcopy(R.load_cell("fedstep.yi-6b-2l.4pod-ring-q8"))
        c["cfg"].update(tiny.SIZES["fedstep.yi-6b-2l.1pod"])
        c["wl"]["traffic"].update(batch=2, seq=16)
        c["wl"]["limits"] = {{"loss_gap": 1e-4, "grad1_gap": 1e-4, "change3_gap": 0.01}}
        return c

    def run():
        r = R.execute(cell(), 2**31 + 11, 0.3, False, check_devices=False, peak=tiny.PEAK)
        return {{"correct": r["correct"], "failed": r["failed"],
                 "checks": {{k: v["value"] for k, v in r["checks"].items()}}}}

    out["sound"] = run()
    from repro.dist import steps
    make, make_gossip = steps.make_fed_train_step, steps.make_gossip_step
    for fault in ("unchanged", "half_batch", "no_gossip"):
        def broken(*a, **k):
            fn, specs, abstract = make(*a, **k)
            def step_fn(params, vel, batch, step, key):
                if fault == "unchanged":
                    return params, vel, fn(params, vel, batch, step, key)[2]
                half = {{n: v[:, : v.shape[1] // 2] for n, v in batch.items()}}
                return fn(params, vel, half, step, key)
            return step_fn, specs, abstract

        def no_gossip(*a, **k):
            g, specs, abstract = make_gossip(*a, **k)
            return (lambda params, key: params), specs, abstract

        steps.make_fed_train_step, steps.make_gossip_step = make, make_gossip
        if fault == "no_gossip":
            steps.make_gossip_step = no_gossip
        else:
            steps.make_fed_train_step = broken
        out[fault] = run()
    print("PODS " + json.dumps(out))
""")


@pytest.fixture(scope="module")
def pods():
    r = subprocess.run([sys.executable, "-c", SCRIPT.format(root=ROOT)], capture_output=True,
                       text=True, timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("PODS ")]
    assert line, r.stdout[-2000:] + r.stderr[-3000:]
    return json.loads(line[0][5:])


def test_ring_mixing_is_the_dense_mixing_matrix(pods):
    # float32: three terms summed in a different order
    assert pods["dense_gap"] < 1e-6
    # the 8-bit wire moves each received value by at most a grid step
    assert 0 < pods["q8_gap"] < 0.1


def test_sound_run_is_correct(pods):
    assert pods["sound"]["correct"] and pods["sound"]["failed"] == 0, pods["sound"]


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_gossip"])
def test_broken_step_is_not_correct(pods, fault):
    assert not pods[fault]["correct"], pods[fault]
