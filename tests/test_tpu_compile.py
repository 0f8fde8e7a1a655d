"""The round path's kernels and the pod gossip program, compiled for a
described TPU v5e (no chip needed): what interpret mode cannot show, such as
block shapes and memory spaces the TPU compiler refuses. Compiling is not
running, so these tests say nothing about results or speed."""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means no TPU compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's executables cannot be read back from the cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def fnn_spec():
    """FlatSpec of fnn_mnist's 3FNN (784-200-200-10)."""
    from repro.core.flatten import make_flat_spec
    from repro.models import make_fnn

    model = make_fnn((200, 200))
    return make_flat_spec(jax.eval_shape(model.init, jax.random.PRNGKey(0)))


def _hlo(fn, *args):
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("bits", [8, 4])
@pytest.mark.parametrize("with_base", [False, True])
def test_payload_qdq_compiles_to_kernel(one_chip, fnn_spec, bits, with_base):
    """The protocol round's fused qdq on an n=100 payload of the 3FNN
    (155,900 rows of 128 lanes), per-message, with and without the fused
    receiver base."""
    from repro.kernels.quantize import payload_quantize_dequantize

    rows = 100 * fnn_spec.rows
    assert rows == 155_900
    payload = jax.ShapeDtypeStruct((100, fnn_spec.d_pad), jnp.float32,
                                   sharding=one_chip)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one_chip)

    def qdq(p, k, base=None):
        return payload_quantize_dequantize(p, fnn_spec, per_message=True,
                                           bits=bits, key=k, base=base,
                                           interpret=False)

    args = (payload, key) + ((payload,) if with_base else ())
    assert "tpu_custom_call" in _hlo(qdq, *args)


def test_qdq_rows_kernel_with_drawn_uniforms_compiles(one_chip):
    from repro.kernels.quantize.quantize import ROW_TILE, qdq_rows_kernel_call

    rows = 4 * ROW_TILE
    w = jax.ShapeDtypeStruct((rows, 128), jnp.float32, sharding=one_chip)
    per_row = jax.ShapeDtypeStruct((rows,), jnp.float32, sharding=one_chip)

    def qdq(w2d, u2d, s_rows, norm_rows):
        return qdq_rows_kernel_call(w2d, u2d, s_rows, norm_rows, bits=8)

    assert "tpu_custom_call" in _hlo(qdq, w, w, per_row, per_row)


@pytest.mark.parametrize("bits", [32, 8])
def test_ring_gossip_on_four_chips_is_collective_permute_only(topo, bits):
    """DFedRW's claim at pod scale: the gossip mix moves params between
    chips by collective-permute and never all-reduces."""
    from repro.dist.gossip import GossipConfig, gossip_mix
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((4,), ("pod",), topo.devices)
    spec = P("pod", None, None)
    tree = {"w": jax.ShapeDtypeStruct((4, 1024, 128), jnp.float32,
                                      sharding=NamedSharding(mesh, spec)),
            "b": jax.ShapeDtypeStruct((4, 256), jnp.float32,
                                      sharding=NamedSharding(mesh, P("pod", None)))}
    specs = {"w": spec, "b": P("pod", None)}
    cfg = GossipConfig(axis="pod", topology="ring", quant_bits=bits)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32,
                               sharding=NamedSharding(mesh, P()))
    text = _hlo(lambda t, k: gossip_mix(t, specs, mesh, cfg, k), tree, key)
    assert "collective-permute" in text
    assert "all-reduce" not in text
