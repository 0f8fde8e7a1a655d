"""Decode path == train forward (logits) for every layer family: the KV
cache / recurrent-state serving path is numerically the same model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.config import ArchConfig, MLAConfig, MoEConfig, SSMConfig
from repro.models import transformer as T

CASES = {
    "dense-gqa-bias": ArchConfig(name="d", n_layers=2, d_model=64, n_heads=4,
                                 n_kv_heads=2, d_ff=128, vocab=64, qkv_bias=True),
    "mqa": ArchConfig(name="q", n_layers=2, d_model=64, n_heads=4, n_kv_heads=1,
                      d_ff=128, vocab=64),
    "mla": ArchConfig(name="m", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                      d_ff=128, vocab=64, attn_type="mla",
                      mla=MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)),
    "ssm": ArchConfig(name="s", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                      d_ff=0, vocab=64, block_pattern=("mamba",), ffn_pattern=("none",),
                      ssm=SSMConfig(state_dim=16, head_dim=16, chunk=8), tie_embeddings=True),
    "hybrid-moe": ArchConfig(name="h", n_layers=4, d_model=64, n_heads=4, n_kv_heads=2,
                             d_ff=128, vocab=64, block_pattern=("mamba", "attn"),
                             ffn_pattern=("dense", "moe"),
                             moe=MoEConfig(n_experts=4, top_k=2),
                             ssm=SSMConfig(state_dim=16, head_dim=16, chunk=8)),
}


@pytest.mark.parametrize("name", list(CASES))
def test_decode_matches_train(name):
    cfg = CASES[name]
    seq = 16
    key = jax.random.PRNGKey(3)
    params = T.init_params(cfg, key, jnp.float32)
    tokens = jax.random.randint(key, (2, seq), 0, cfg.vocab)
    logits_train, _ = T.forward_train(cfg, params, tokens, remat=False)
    cache = T.init_cache(cfg, 2, seq, jnp.float32)
    outs = []
    step = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t))
    for t in range(seq):
        lg, cache = step(params, cache, tokens[:, t:t + 1])
        outs.append(lg[:, 0])
    logits_dec = jnp.stack(outs, axis=1)
    # float32 on the CPU: only summation order differs (the dropless MoE
    # routes every token alone, so one token and the whole sequence agree)
    np.testing.assert_allclose(
        np.asarray(logits_dec), np.asarray(logits_train), atol=2e-3, rtol=2e-3
    )


def test_sliding_window_ring_buffer():
    """Decode beyond the window: ring buffer keeps only the last W tokens,
    matching train-time sliding-window attention on the final position."""
    cfg = CASES["dense-gqa-bias"].with_sliding_window(8)
    seq = 20
    key = jax.random.PRNGKey(4)
    params = T.init_params(cfg, key, jnp.float32)
    tokens = jax.random.randint(key, (1, seq), 0, cfg.vocab)
    logits_train, _ = T.forward_train(cfg, params, tokens, remat=False)
    cache = T.init_cache(cfg, 1, seq, jnp.float32)
    assert cache["slots"]["slot0"]["k"].shape[2] == 8  # ring buffer = window
    out = None
    for t in range(seq):
        out, cache = T.decode_step(cfg, params, cache, tokens[:, t:t + 1])
    np.testing.assert_allclose(
        np.asarray(out[:, 0]), np.asarray(logits_train[:, -1]), atol=2e-3, rtol=2e-3
    )


@pytest.mark.parametrize("name", list(CASES))
def test_prefill_chunk_matches_decode(name):
    """Chunked batched prefill (write-at-offset into the decode cache)
    produces the same logits as the token-at-a-time decode path, for
    mixed-length rows advancing through different chunk counts."""
    cfg = CASES[name]
    lens, chunk, max_len = (5, 11), 4, 16
    b = len(lens)
    key = jax.random.PRNGKey(7)
    params = T.init_params(cfg, key, jnp.float32)
    tokens = np.asarray(jax.random.randint(key, (b, max(lens)), 0, cfg.vocab))

    ref = []  # per-row token-at-a-time logits over its own prompt
    for r, ln in enumerate(lens):
        cache = T.init_cache(cfg, 1, max_len, jnp.float32)
        outs = []
        for t in range(ln):
            lg, cache = T.decode_step(cfg, params, cache, jnp.asarray(tokens[r:r + 1, t:t + 1]))
            outs.append(np.asarray(lg[0, 0]))
        ref.append(np.stack(outs))

    cache = T.init_cache(cfg, b, max_len, jnp.float32)
    pos = np.zeros(b, np.int32)
    done = np.zeros(b, np.int32)
    got = [[] for _ in range(b)]
    while (done < np.asarray(lens)).any():
        buf = np.zeros((b, chunk), np.int32)
        nv = np.zeros(b, np.int32)
        for r, ln in enumerate(lens):
            m = min(chunk, ln - done[r])
            nv[r] = m
            if m:
                buf[r, :m] = tokens[r, done[r]:done[r] + m]
        lg, cache = T.prefill_chunk(cfg, params, cache, jnp.asarray(buf),
                                    jnp.asarray(pos), jnp.asarray(nv))
        lg = np.asarray(lg)
        for r in range(b):
            got[r].extend(lg[r, j] for j in range(nv[r]))
        pos += nv
        done += nv

    # MoE needs no loose tolerance here: the dropless layer routes every
    # token alone, so a chunk and single tokens compute the same experts.
    for r, ln in enumerate(lens):
        np.testing.assert_allclose(np.stack(got[r]), ref[r], atol=2e-3, rtol=2e-3)


def test_prefill_chunk_sliding_window():
    """Chunked prefill through a ring buffer smaller than the prompt:
    wraps must keep matching the sequential sliding-window decode."""
    cfg = CASES["dense-gqa-bias"].with_sliding_window(6)
    seq, chunk = 17, 5
    key = jax.random.PRNGKey(9)
    params = T.init_params(cfg, key, jnp.float32)
    tokens = jax.random.randint(key, (1, seq), 0, cfg.vocab)
    cache = T.init_cache(cfg, 1, seq, jnp.float32)
    ref = []
    for t in range(seq):
        lg, cache = T.decode_step(cfg, params, cache, tokens[:, t:t + 1])
        ref.append(np.asarray(lg[0, 0]))
    cache = T.init_cache(cfg, 1, seq, jnp.float32)
    got = []
    for start in range(0, seq, chunk):
        m = min(chunk, seq - start)
        buf = np.zeros((1, chunk), np.int32)
        buf[0, :m] = np.asarray(tokens[0, start:start + m])
        lg, cache = T.prefill_chunk(cfg, params, cache, jnp.asarray(buf),
                                    jnp.asarray([start], np.int32),
                                    jnp.asarray([m], np.int32))
        got.extend(np.asarray(lg[0, j]) for j in range(m))
    np.testing.assert_allclose(np.stack(got), np.stack(ref), atol=2e-3, rtol=2e-3)


def test_prefill_inactive_rows_untouched():
    """n_valid=0 rows (decoding/free slots riding along in the fixed-shape
    prefill call) must leave every cache leaf of that row bit-unchanged."""
    cfg = CASES["hybrid-moe"]
    b, chunk, max_len = 3, 4, 16
    key = jax.random.PRNGKey(11)
    params = T.init_params(cfg, key, jnp.float32)
    cache = T.init_cache(cfg, b, max_len, jnp.float32)
    # put some real state into every row first
    warm = jax.random.randint(key, (b, chunk), 0, cfg.vocab)
    _, cache = T.prefill_chunk(cfg, params, cache, warm,
                               jnp.zeros(b, jnp.int32), jnp.full(b, chunk, jnp.int32))
    buf = jax.random.randint(key, (b, chunk), 0, cfg.vocab)
    nv = jnp.asarray([chunk, 0, 2], jnp.int32)
    _, cache2 = T.prefill_chunk(cfg, params, cache, buf,
                                jnp.full(b, chunk, jnp.int32), nv)
    for leaf, leaf2 in zip(jax.tree_util.tree_leaves(cache["slots"]),
                           jax.tree_util.tree_leaves(cache2["slots"])):
        # row 1 inactive: bit-identical; row 0 active: must have changed
        np.testing.assert_array_equal(np.asarray(leaf[:, 1]), np.asarray(leaf2[:, 1]))
    changed = any(
        not np.array_equal(np.asarray(l1[:, 0]), np.asarray(l2[:, 0]))
        for l1, l2 in zip(jax.tree_util.tree_leaves(cache["slots"]),
                          jax.tree_util.tree_leaves(cache2["slots"])))
    assert changed


def test_encdec_decode_consistency():
    cfg = ArchConfig(name="ed", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                     d_ff=128, vocab=64, enc_dec=True, n_enc_layers=2,
                     frontend="audio", frontend_tokens=12)
    seq = 10
    key = jax.random.PRNGKey(5)
    params = T.init_params(cfg, key, jnp.float32)
    tokens = jax.random.randint(key, (2, seq), 0, cfg.vocab)
    embeds = jax.random.normal(key, (2, 12, cfg.d_model), jnp.float32)
    logits_train, _ = T.forward_train(cfg, params, tokens, embeds, remat=False)
    from repro.models.transformer import _run_encoder
    cache = T.init_cache(cfg, 2, seq, jnp.float32, enc_len=12)
    cache["enc_out"] = _run_encoder(cfg, params, embeds, remat=False)
    outs = []
    for t in range(seq):
        lg, cache = T.decode_step(cfg, params, cache, tokens[:, t:t + 1])
        outs.append(lg[:, 0])
    np.testing.assert_allclose(
        np.asarray(jnp.stack(outs, 1)), np.asarray(logits_train), atol=2e-3, rtol=2e-3
    )
