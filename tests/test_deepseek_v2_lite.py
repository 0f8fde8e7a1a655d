"""DeepSeek-V2-Lite's layers against the plain reference
(``chipbench/refs/mla_moe.py``, which imports nothing of the program) on
the CPU at smoke sizes: the pod step's loss and first gradient, the
dropless expert layer, the expert shares of an expert-parallel
deployment, YaRN, and prefill then decode through the cache."""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from chipbench.refs import mla_moe  # noqa: E402
from repro.configs import get_arch, get_smoke  # noqa: E402
from repro.models import layers as L  # noqa: E402
from repro.models import transformer as T  # noqa: E402
from repro.models.config import MoEConfig  # noqa: E402

ARCH = "deepseek-v2-lite-16b"


def _ref_config(cfg) -> dict:
    """The reference's description of an ``ArchConfig``."""
    mo, ml, y = cfg.moe, cfg.mla, cfg.yarn
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.n_heads,
            "qk_nope_head_dim": ml.qk_nope_dim, "qk_rope_head_dim": ml.qk_rope_dim,
            "v_head_dim": ml.v_head_dim, "kv_lora_rank": ml.kv_lora_rank,
            "intermediate_size": cfg.d_ff, "moe_intermediate_size": mo.d_expert,
            "first_k_dense_replace": cfg.n_dense_layers, "num_hidden_layers": cfg.n_layers,
            "router_experts": mo.n_experts, "n_routed_experts": mo.held,
            "expert_start": mo.expert_start, "num_experts_per_tok": mo.top_k,
            "n_shared_experts": mo.n_shared, "vocab_size": cfg.vocab,
            "rms_norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "aux_loss_alpha": mo.router_aux_weight,
            "rope_scaling": {"factor": y.factor, "original_max_position_embeddings":
                             y.original_max_positions, "beta_fast": y.beta_fast,
                             "beta_slow": y.beta_slow, "mscale": y.mscale,
                             "mscale_all_dim": y.mscale_all_dim}}


def _held(cfg, start: int, n: int):
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, expert_start=start,
                                                            n_held=n))


def _batch(cfg, b=2, l=24, seed=0):
    toks = jax.random.randint(jax.random.PRNGKey(seed), (b, l + 1), 0, cfg.vocab)
    return {"tokens": toks[:, :-1], "labels": toks[:, 1:]}


def test_config_is_the_published_one():
    cfg = get_arch(ARCH)
    assert (cfg.n_dense_layers, cfg.d_ff, cfg.moe.d_expert, cfg.n_blocks) == (1, 10944, 1408, 26)
    assert not cfg.moe.norm_topk and cfg.moe.aux == "seq" and cfg.moe.router_aux_weight == 0.001
    assert cfg.yarn.factor == 40 and cfg.yarn.original_max_positions == 4096
    assert get_smoke(ARCH).n_dense_layers == 1


def test_yarn_frequencies_and_softmax_scale():
    """low 10 and high 23 for the 64 rope dimensions at base 1e4 over 4096
    positions; the softmax scale grows by mscale(40, 0.707)^2 = 1.5896."""
    y = get_arch(ARCH).yarn
    inv, low, high = L.yarn_inv_freq(64, 1e4, y)
    rs = _ref_config(get_arch(ARCH))["rope_scaling"]
    ref, rlow, rhigh = mla_moe.yarn_inv_freq(64, 1e4, rs)
    assert (low, high) == (rlow, rhigh) == (10, 23)
    np.testing.assert_array_equal(inv, ref)
    base = 1.0 / 1e4 ** (np.arange(0, 64, 2) / 64)
    np.testing.assert_allclose(inv[:low], base[:low], rtol=1e-6)          # kept
    np.testing.assert_allclose(inv[high:], base[high:] / 40, rtol=1e-6)   # interpolated
    assert L.yarn_mscale(40, 0.707) ** 2 == pytest.approx(1.5896, abs=1e-4)
    cos, sin = L.rope_freqs(jnp.arange(5), 64, 1e4, y)
    np.testing.assert_allclose(cos ** 2 + sin ** 2, 1.0, rtol=1e-6)      # mscale / mscale_all = 1


@pytest.mark.parametrize("held", [(0, 0), (1, 2)], ids=["all", "share"])
def test_fed_step_matches_reference(held):
    """Loss, logits and the first gradient of the smoke shape through
    ``make_fed_train_step``: float32 on the CPU, so only summation order
    differs from the reference (1e-4 relative; bf16 storage alone would
    move them by 4e-3)."""
    from repro.dist.gossip import GossipConfig
    from repro.dist.steps import make_fed_train_step
    from repro.launch.mesh import make_mesh

    cfg = _held(get_smoke(ARCH), *held) if held[1] else get_smoke(ARCH)
    m = _ref_config(cfg)
    params = mla_moe.init(m, jax.random.PRNGKey(1))
    assert (jax.tree_util.tree_structure(params)
            == jax.tree_util.tree_structure(T.abstract_params(cfg, jnp.float32)))
    batch = _batch(cfg)
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"), jax.devices()[:1])
    step, _, _ = make_fed_train_step(cfg, mesh, GossipConfig(axis="pod"), lr_r=100.0,
                                     beta=0.9, remat=True, dtype=jnp.float32)
    stack = lambda t: jax.tree_util.tree_map(lambda a: a[None], t)
    zeros = jax.tree_util.tree_map(jnp.zeros_like, stack(params))
    with jax.default_matmul_precision("highest"), mesh:
        _, vel, losses = jax.jit(step)(stack(params), zeros, stack(batch), jnp.int32(0),
                                       jax.random.PRNGKey(0))
        loss, grads = jax.value_and_grad(mla_moe.loss)(params, batch, m)
        logits, aux = T.forward_train(cfg, params, batch["tokens"], remat=False)
        ref_logits, ref_aux = mla_moe.forward(params, batch["tokens"], m)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits), rtol=1e-4, atol=1e-4)
    assert float(aux) == pytest.approx(float(ref_aux), rel=1e-4) and float(aux) > 0
    assert float(losses[0]) == pytest.approx(float(loss), rel=1e-5)
    for g, r in zip(jax.tree_util.tree_leaves(vel), jax.tree_util.tree_leaves(grads)):
        np.testing.assert_allclose(np.asarray(g[0]), np.asarray(r), rtol=1e-3,
                                   atol=1e-4 * float(jnp.max(jnp.abs(r))))


def test_dropless_when_every_token_picks_the_same_experts():
    """A router biased so that every token's top-2 are experts 1 and 2 (of
    the held 0..3): a capacity of L*k/E rows an expert would drop half of
    them; the layer keeps all and matches the reference."""
    cfg = get_smoke(ARCH)
    m = _ref_config(cfg)
    p = jax.tree_util.tree_map(lambda a: a[0], mla_moe.init(m, jax.random.PRNGKey(2))
                               ["blocks"]["slot0"]["ffn"])
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, cfg.d_model), jnp.float32) + 1.0
    p["router"] = p["router"].at[:, 1:3].add(10.0)                # sum(x) > 0: biased
    with jax.default_matmul_precision("highest"):
        y, aux = L.moe_apply(p, x, cfg)
        ref, ref_aux = mla_moe._moe(p, x, m)
        _, idx = jax.lax.top_k(jax.nn.softmax(x @ p["router"]), 2)
    assert bool(jnp.all(jnp.sort(idx, axis=-1) == jnp.array([1, 2])))
    # float32 on the CPU: only summation order differs
    np.testing.assert_allclose(np.asarray(y), np.asarray(ref), rtol=1e-5, atol=1e-5)
    assert float(aux) == pytest.approx(float(ref_aux), rel=1e-6)


def test_expert_shares_add_up_to_the_whole_layer():
    """Eight chips holding experts 0-7, 8-15, ..., 56-63 of a 64-expert
    top-6 layer: their routed parts, with the shared experts counted once,
    add up to the uncut layer (and to the uncut reference)."""
    d, de = 32, 16
    cfg = dataclasses.replace(
        get_smoke(ARCH), d_model=d,
        moe=MoEConfig(n_experts=64, top_k=6, n_shared=2, d_expert=de, norm_topk=False,
                      aux="seq", router_aux_weight=0.001))
    whole = L.init_moe(jax.random.PRNGKey(4), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(5), (2, 16, d), jnp.float32)
    shared = L.ffn_apply(whole["shared"], x)
    parts = []
    with jax.default_matmul_precision("highest"):
        for start in range(0, 64, 8):
            share = dict(whole, **{n: whole[n][start:start + 8]
                                   for n in ("w_gate", "w_up", "w_down")})
            y, _ = L.moe_apply(share, x, _held(cfg, start, 8))
            parts.append(y - shared)
        y_whole, _ = L.moe_apply(whole, x, cfg)
        m = dict(_ref_config(cfg), router_experts=64, n_routed_experts=64, expert_start=0)
        ref, _ = mla_moe._moe(whole, x, m)
    total = sum(parts) + shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(y_whole), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(ref), rtol=1e-5, atol=1e-5)


def test_prefill_then_decode_matches_forward():
    """The smoke config with its leading dense layer: a chunked prefill of
    the prompt, then token-at-a-time decode through the cache, gives the
    logits of ``forward_train`` (float32 on the CPU: summation order)."""
    cfg = get_smoke(ARCH)
    params = T.init_params(cfg, jax.random.PRNGKey(6), jnp.float32)
    tokens = jax.random.randint(jax.random.PRNGKey(7), (2, 12), 0, cfg.vocab)
    want, _ = T.forward_train(cfg, params, tokens, remat=False)
    cache = T.init_cache(cfg, 2, 16, jnp.float32)
    assert cache["dense"]["c_kv"].shape[0] == cfg.n_dense_layers
    pre, cache = T.prefill_chunk(cfg, params, cache, tokens[:, :8], jnp.zeros(2, jnp.int32),
                                 jnp.full(2, 8, jnp.int32))
    got = [pre]
    for t in range(8, 12):
        lg, cache = T.decode_step(cfg, params, cache, tokens[:, t:t + 1],
                                  positions=jnp.full(2, t, jnp.int32))
        got.append(lg)
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, axis=1)), np.asarray(want),
                               rtol=2e-3, atol=2e-3)
