"""Sharding rule engine tests: every assigned axis divides its dim, row/col
parallel conventions hold, odd dims fall back to replication."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCH_IDS, get_arch
from repro.dist.sharding import batch_specs, cache_specs, param_specs, spec_for_leaf
from repro.models import transformer as T


def _mesh(shape, names):
    return jax.sharding.AbstractMesh(shape, names)


MESH = _mesh((16, 16), ("data", "model"))
MESH3 = _mesh((2, 16, 16), ("pod", "data", "model"))


def _axis_size(mesh, ax):
    if isinstance(ax, tuple):
        out = 1
        for a in ax:
            out *= mesh.shape[a]
        return out
    return mesh.shape[ax]


def _check_divisible(specs, tree, mesh):
    flat_s = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    flat_t = jax.tree_util.tree_leaves(tree)
    assert len(flat_s) == len(flat_t)
    for spec, leaf in zip(flat_s, flat_t):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is not None:
                assert dim % _axis_size(mesh, ax) == 0, (leaf.shape, spec)


@pytest.mark.parametrize("arch_id", ARCH_IDS)
@pytest.mark.parametrize("mesh", [MESH, MESH3], ids=["16x16", "2x16x16"])
def test_param_specs_divisible(arch_id, mesh):
    cfg = get_arch(arch_id)
    abstract = T.abstract_params(cfg)
    specs = param_specs(abstract, mesh)
    _check_divisible(specs, abstract, mesh)


def test_row_col_parallel_convention():
    # column-parallel: model axis on output dim
    assert spec_for_leaf("blocks/slot0/mixer/wq", (8, 8192, 8192), MESH, 1) == P(None, "data", "model")
    # row-parallel: model axis on input dim
    assert spec_for_leaf("blocks/slot0/mixer/wo", (8, 8192, 8192), MESH, 1) == P(None, "model", "data")
    # norm scales replicated
    assert spec_for_leaf("blocks/slot0/norm1", (8, 8192), MESH, 1) == P(None, None)


def test_expert_parallel_when_divisible():
    # 16 experts on a 16-way model axis -> expert parallel
    s = spec_for_leaf("blocks/slot1/ffn/w_gate", (9, 16, 8192, 24576), MESH, 1)
    assert s[1] == "model"
    # 8 experts not divisible by 16 -> tensor parallel inside experts
    s8 = spec_for_leaf("blocks/slot0/ffn/w_gate", (64, 8, 6144, 32768), MESH, 1)
    assert s8[1] != "model" and "model" in tuple(s8)


def test_odd_vocab_replicates():
    # internvl2 vocab 151655 (odd) cannot shard 16 ways on either dim role
    s = spec_for_leaf("embed", (151655, 896), MESH, 0)
    assert s[0] is None and s[1] == "model"  # d=896 divisible by 16


def test_batch_specs_paths():
    mesh = MESH
    b = {"tokens": jax.ShapeDtypeStruct((256, 4096), jnp.int32)}
    s = batch_specs(b, mesh)["tokens"]
    assert s[0] == "data"
    # batch=1 long-context: falls back to sequence sharding
    b1 = {"tokens": jax.ShapeDtypeStruct((1, 524288), jnp.int32)}
    s1 = batch_specs(b1, mesh)["tokens"]
    assert s1[0] is None and s1[1] == "data"


def test_cache_specs_long_context():
    cfg = get_arch("yi-6b").with_sliding_window(8192)
    cache = jax.eval_shape(lambda: T.init_cache(cfg, 1, 524288, jnp.bfloat16))
    specs = cache_specs(cache, MESH)
    k_spec = specs["slots"]["slot0"]["k"]
    assert k_spec[0] is None  # n_blocks stack dim never sharded
    _check_divisible(
        {"slots": specs["slots"]}, {"slots": cache["slots"]}, MESH
    )


@pytest.mark.parametrize("arch_id", ["yi-6b", "mamba2-130m", "grok-1-314b",
                                     "seamless-m4t-large-v2", "deepseek-v2-lite-16b"])
def test_cache_specs_divisible(arch_id):
    cfg = get_arch(arch_id)
    cache = jax.eval_shape(
        lambda: T.init_cache(cfg, 128, 32768, jnp.bfloat16,
                             enc_len=cfg.frontend_tokens if cfg.enc_dec else 0)
    )
    specs = cache_specs(cache, MESH)
    flat_s = jax.tree_util.tree_leaves(specs, is_leaf=lambda x: isinstance(x, P))
    flat_t = jax.tree_util.tree_leaves(cache)
    for spec, leaf in zip(flat_s, flat_t):
        for dim, ax in zip(leaf.shape, tuple(spec)):
            if ax is not None:
                assert dim % _axis_size(MESH, ax) == 0, (arch_id, leaf.shape, spec)
