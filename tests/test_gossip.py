"""Gossip aggregation tests. Multi-device semantics run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
keeps the host's single device, per the dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.dist.gossip import GossipConfig, make_expander_weights, make_ring_weights

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_weights_sum_to_one():
    for n in (2, 3, 8, 16):
        w = make_ring_weights(n)
        assert abs(sum(x for _, x in w) - 1.0) < 1e-12
        cfg = GossipConfig(topology="expander")
        we = make_expander_weights(n, cfg)
        assert abs(sum(x for _, x in we) - 1.0) < 1e-12
        offs = [o for o, _ in we]
        assert len(set(offs)) == len(offs)


def test_offsets_valid():
    cfg = GossipConfig(topology="expander")
    for n in (2, 4, 8, 16):
        for o in cfg.offsets(n):
            assert 0 < o < n
    assert GossipConfig(topology="all").offsets(4) == [1, 2, 3]
    assert GossipConfig(topology="ring").offsets(2) == [1]


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.dist.gossip import GossipConfig, gossip_mix, walk_permute_batch

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8,), ("pod",))
    x = jnp.arange(8 * 4, dtype=jnp.float32).reshape(8, 4)
    spec = P("pod", None)
    xs = jax.device_put(x, NamedSharding(mesh, spec))

    # 1) full-precision ring mix == dense reference
    cfg = GossipConfig(axis="pod", topology="ring", quant_bits=32)
    out = gossip_mix({{"w": xs}}, {{"w": spec}}, mesh, cfg)["w"]
    W = np.zeros((8, 8))
    for i in range(8):
        for off, wgt in [(0, 1/3), (1, 1/3), (7, 1/3)]:
            W[(i + off) % 8, i] += wgt   # receiver i gets shard from i+off
    ref = W.T @ np.asarray(x)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=1e-5)

    # 2) mean preservation (doubly stochastic mixing)
    np.testing.assert_allclose(np.asarray(out).mean(0), np.asarray(x).mean(0), rtol=1e-5)

    # 3) quantized mix close to full precision, still mean-preserving in expectation
    cfgq = GossipConfig(axis="pod", topology="ring", quant_bits=8)
    outq = gossip_mix({{"w": xs}}, {{"w": spec}}, mesh, cfgq, key=jax.random.PRNGKey(0))["w"]
    err = np.abs(np.asarray(outq) - ref).max()
    scale = np.abs(ref).max()
    assert err < 0.05 * scale + 1.0, (err, scale)

    # 4) walk permute moves shards by one hop
    moved = walk_permute_batch({{"t": xs}}, {{"t": spec}}, mesh, "pod", offset=1)["t"]
    np.testing.assert_allclose(np.asarray(moved), np.roll(np.asarray(x), 1, axis=0))
    print("GOSSIP_OK")
""")


@pytest.mark.slow
def test_gossip_mix_multidevice():
    code = _SUBPROC.format(src=os.path.abspath(SRC))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=300)
    assert "GOSSIP_OK" in r.stdout, r.stdout + r.stderr
