"""Deliverable (f): per-assigned-architecture SMOKE tests -- a reduced
same-family config (<= 2 pattern repeats, d_model <= 512, <= 4 experts) runs
one forward/train step and one decode step on CPU; output shapes + no NaNs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_arch, get_smoke
from repro.models import transformer as T


def _batch_for(cfg, batch=2, seq=32, key=None):
    key = key or jax.random.PRNGKey(0)
    b = {}
    if cfg.enc_dec:
        b["tokens"] = jax.random.randint(key, (batch, seq), 0, cfg.vocab)
        b["embeds"] = jax.random.normal(key, (batch, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    elif cfg.frontend != "none":
        s_text = max(seq - cfg.frontend_tokens, 4)
        b["tokens"] = jax.random.randint(key, (batch, s_text), 0, cfg.vocab)
        b["embeds"] = jax.random.normal(key, (batch, cfg.frontend_tokens, cfg.d_model), jnp.float32)
    else:
        b["tokens"] = jax.random.randint(key, (batch, seq), 0, cfg.vocab)
    b["labels"] = b["tokens"]
    return b


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_reduced_variant(arch_id):
    cfg = get_smoke(arch_id)
    assert cfg.d_model <= 512
    if cfg.moe is not None:
        assert cfg.moe.n_experts <= 4
    key = jax.random.PRNGKey(0)
    params = T.init_params(cfg, key, jnp.float32)
    batch = _batch_for(cfg)

    # one train step: loss + grads finite
    def lf(p):
        return T.loss_fn(cfg, p, batch, remat=False)

    loss, grads = jax.value_and_grad(lf)(params)
    assert jnp.isfinite(loss), arch_id
    gnorm = sum(jnp.sum(g.astype(jnp.float32) ** 2) for g in jax.tree_util.tree_leaves(grads))
    assert jnp.isfinite(gnorm) and gnorm > 0.0, arch_id

    # forward shapes
    logits, aux = T.forward_train(cfg, params, batch["tokens"], batch.get("embeds"), remat=False)
    assert logits.shape == (*batch["tokens"].shape, cfg.vocab), arch_id
    assert bool(jnp.isfinite(logits).all()), arch_id


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_smoke_decode_step(arch_id):
    cfg = get_smoke(arch_id)
    key = jax.random.PRNGKey(1)
    params = T.init_params(cfg, key, jnp.float32)
    cache = T.init_cache(cfg, 2, 64, jnp.float32,
                         enc_len=cfg.frontend_tokens if cfg.enc_dec else 0)
    tok = jax.random.randint(key, (2, 1), 0, cfg.vocab)
    logits, cache2 = T.decode_step(cfg, params, cache, tok)
    assert logits.shape == (2, 1, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), arch_id
    assert int(cache2["pos"]) == 1
    logits3, _ = T.decode_step(cfg, params, cache2, tok)
    assert bool(jnp.isfinite(logits3).all())


@pytest.mark.parametrize("arch_id", ARCH_IDS)
def test_full_config_matches_assignment(arch_id):
    """The FULL configs carry the exact assigned hyperparameters."""
    cfg = get_arch(arch_id)
    expect = {
        "jamba-1.5-large-398b": (72, 8192, 64, 8, 24576, 65536),
        "deepseek-v2-lite-16b": (27, 2048, 16, 16, 10944, 102400),
        "mamba2-130m": (24, 768, 12, 12, 0, 50280),
        "qwen2-72b": (80, 8192, 64, 8, 29568, 152064),
        "yi-6b": (32, 4096, 32, 4, 11008, 64000),
        "internvl2-1b": (24, 896, 14, 2, 4864, 151655),
        "granite-34b": (88, 6144, 48, 1, 24576, 49152),
        "qwen2.5-32b": (64, 5120, 40, 8, 27648, 152064),
        "grok-1-314b": (64, 6144, 48, 8, 32768, 131072),
        "seamless-m4t-large-v2": (24, 1024, 16, 16, 8192, 256206),
    }[arch_id]
    got = (cfg.n_layers, cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.vocab)
    assert got == expect, (arch_id, got, expect)
    assert cfg.citation


def test_moe_configs():
    ds = get_arch("deepseek-v2-lite-16b")
    assert ds.moe.n_experts == 64 and ds.moe.top_k == 6 and ds.moe.n_shared == 2
    gk = get_arch("grok-1-314b")
    assert gk.moe.n_experts == 8 and gk.moe.top_k == 2
    jb = get_arch("jamba-1.5-large-398b")
    assert jb.moe.n_experts == 16 and jb.moe.top_k == 2
    assert jb.block_pattern.count("attn") == 1 and len(jb.block_pattern) == 8


def test_param_count_targets():
    """Analytic totals land near the advertised sizes."""
    for arch_id, target_b, tol in [
        ("jamba-1.5-large-398b", 398, 0.05),
        ("qwen2-72b", 72, 0.05),
        ("grok-1-314b", 314, 0.05),
        ("mamba2-130m", 0.130, 0.10),
        ("deepseek-v2-lite-16b", 16, 0.10),
        ("yi-6b", 6, 0.10),
    ]:
        got = get_arch(arch_id).param_count() / 1e9
        assert abs(got - target_b) / target_b < tol, (arch_id, got, target_b)
