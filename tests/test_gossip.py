"""Gossip aggregation tests. Multi-device semantics run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the main test process
keeps the host's single device, per the dry-run isolation rule)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.dist.gossip import (GossipConfig, gossip_mix, make_expander_weights,
                               make_ring_weights, wire_bytes)

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


_WIRE_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P, NamedSharding
    from repro.core.quantization import QuantConfig, dequantize, quantize
    from repro.dist.gossip import GossipConfig, gossip_mix
    from repro.launch.mesh import make_mesh

    bits, n = {bits}, 4
    mesh = make_mesh((n,), ("pod",), jax.devices()[:n])
    k0, k1 = jax.random.split(jax.random.PRNGKey(3))
    tree = {{"b": jax.random.normal(k0, (n, 24)),
             "w": jax.random.normal(k1, (n, 16, 8)) * 3.0}}
    specs = {{"b": P("pod", None), "w": P("pod", None, None)}}
    placed = {{k: jax.device_put(v, NamedSharding(mesh, specs[k])) for k, v in tree.items()}}
    key = jax.random.PRNGKey(11)
    cfg = GossipConfig(axis="pod", topology="ring", quant_bits=bits)
    got = gossip_mix(placed, specs, mesh, cfg, key)

    # the old wire: Q^-1(Q(x)) on the sender, a roll by each offset, 1/3 each
    for li, name in enumerate(sorted(tree)):
        x = tree[name]
        acc = (1 / 3) * x
        for oi, off in enumerate((1, n - 1)):
            sent = x
            if bits < 32:
                rows = []
                for me in range(n):
                    k = jax.random.fold_in(jax.random.fold_in(jax.random.fold_in(
                        key, li), oi), me)
                    q = quantize(x[me:me + 1], QuantConfig(bits=bits), k)
                    rows.append(dequantize(q, dtype=x.dtype))
                sent = jnp.concatenate(rows)
            acc = acc + (1 / 3) * jnp.roll(sent, -off, axis=0)
        np.testing.assert_array_equal(np.asarray(got[name]), np.asarray(acc))
    print("WIRE_OK")
""")


@pytest.mark.parametrize("bits", [8, 4, 32])
def test_gossip_wire_matches_sender_round_trip(bits):
    """Sending int8 codes and the (s, norm) header, dequantized on the
    receiver, mixes the same floats bit for bit as dequantizing on the
    sender and permuting the float payload."""
    code = _WIRE_SUBPROC.format(src=os.path.abspath(SRC), bits=bits)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=300)
    assert "WIRE_OK" in r.stdout, r.stdout + r.stderr


def test_gossip_mix_refuses_widths_int8_cannot_carry():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1,), ("pod",), jax.devices()[:1])
    cfg = GossipConfig(axis="pod", quant_bits=16)
    with pytest.raises(ValueError, match="bit-width"):
        gossip_mix({"w": jnp.zeros((1, 4))}, {"w": P("pod", None)}, mesh, cfg,
                   jax.random.PRNGKey(0))


@pytest.mark.parametrize("bits", [8, 32])
def test_wire_bytes_counts_codes_and_headers(bits):
    import jax
    import jax.numpy as jnp

    tree = {"b": jax.ShapeDtypeStruct((4, 24), jnp.float32),
            "w": jax.ShapeDtypeStruct((4, 16, 8), jnp.bfloat16)}
    cfg = GossipConfig(axis="pod", topology="ring", quant_bits=bits)
    # two ring edges; a pod holds 24 + 128 elements
    want = 2 * ((24 + 8) + (128 + 8)) if bits == 8 else 2 * (24 * 4 + 128 * 2)
    assert wire_bytes(tree, cfg, 4) == want
    assert wire_bytes(tree, cfg, 1) == 0
