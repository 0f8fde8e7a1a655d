"""Sharding rule-engine edge cases beyond the seed spec tests: 1-D leaves,
GQA K/V whose flattened head dim does not divide the model axis, federated
batch specs, and the named() device_put round trip on the host mesh."""
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.dist.sharding import batch_specs, named, param_specs, spec_for_leaf
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.config import ArchConfig


def _mesh(shape, names):
    return jax.sharding.AbstractMesh(shape, names)


MESH = _mesh((16, 16), ("data", "model"))


def test_1d_leaves_replicate():
    # norm scales, qkv biases, and SSM per-head params are all replicated,
    # stacked or not.
    assert spec_for_leaf("final_norm", (8192,), MESH, 0) == P(None)
    assert spec_for_leaf("blocks/slot0/mixer/bq", (8, 8192), MESH, 1) == P(None, None)
    assert spec_for_leaf("blocks/slot0/mixer/A_log", (8, 256), MESH, 1) == P(None, None)
    assert spec_for_leaf("blocks/slot0/mixer/conv_b", (8, 1792), MESH, 1) == P(None, None)


def test_gqa_kv_smaller_than_model_axis():
    # MQA-style K/V: kv_heads * head_dim = 1 * 24 does not divide the 16-way
    # model axis -> the model axis falls back to the input (d_model) dim.
    s = spec_for_leaf("blocks/slot0/mixer/wk", (8, 4096, 24), MESH, 1)
    assert s == P(None, "model", None)
    # Divisible flattened K/V (kv=1, hd=64) stays column-parallel.
    s = spec_for_leaf("blocks/slot0/mixer/wv", (8, 6144, 64), MESH, 1)
    assert s == P(None, "data", "model")
    # Nothing divides -> full replication, never an invalid assignment.
    assert spec_for_leaf("blocks/slot0/mixer/wk", (8, 15, 9), MESH, 1) == P(None, None, None)


def test_batch_specs_fed_axis():
    mesh3 = _mesh((4, 2, 16), ("pod", "data", "model"))
    b = {"tokens": jax.ShapeDtypeStruct((4, 32, 128), jnp.int32)}
    s = batch_specs(b, mesh3, fed_axis="pod")["tokens"]
    assert s == P("pod", "data", None)
    # Group count not divisible by the pod axis -> leading dim replicated.
    b_odd = {"tokens": jax.ShapeDtypeStruct((3, 32, 128), jnp.int32)}
    s_odd = batch_specs(b_odd, mesh3, fed_axis="pod")["tokens"]
    assert s_odd == P(None, "data", None)


def test_named_device_put_round_trip_host_mesh():
    """named(param_specs) must device_put cleanly on a 1x1 host mesh and
    leave values bit-identical (size-1 axes divide everything, so the full
    rule set is exercised end to end)."""
    cfg = ArchConfig(name="tiny", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=129)  # odd vocab on purpose
    mesh = make_mesh((1, 1), ("data", "model"))
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    specs = param_specs(params, mesh)
    placed = jax.device_put(params, named(specs, mesh))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        params, placed)
    flat_p, td_p = jax.tree_util.tree_flatten(placed)
    flat_s = jax.tree_util.tree_leaves(specs, is_leaf=lambda s: isinstance(s, P))
    assert len(flat_p) == len(flat_s)
    for leaf, spec in zip(flat_p, flat_s):
        assert leaf.sharding.spec == spec
