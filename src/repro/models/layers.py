"""Layer primitives for the pod-scale model zoo.

Everything is a pure function over explicit param pytrees (dicts), so layer
blocks can be stacked and scanned (`jax.lax.scan`) for fast lowering of
deep models, and sharded by path-based PartitionSpec rules.

Covers: RMSNorm, RoPE (with YaRN scaling), GQA attention (QKV-bias, MQA,
sliding-window ring cache), MLA (DeepSeek compressed-KV attention), SwiGLU
FFN, a dropless top-k MoE with shared experts that computes the experts it
holds by a grouped matmul (Pallas megablox), and the Mamba2 SSD mixer
(chunked train scan + O(1) recurrent decode state).

Dtype policy: params are stored in `param_dtype` (default bf16), activations
in bf16, softmax/norm statistics in f32.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

from repro.models.config import ArchConfig, YarnConfig
from repro.obs import scopes

__all__ = [
    "rms_norm",
    "rope_freqs",
    "yarn_mscale",
    "yarn_inv_freq",
    "apply_rope",
    "init_attn",
    "attn_train",
    "attn_decode",
    "attn_prefill",
    "init_mla",
    "mla_train",
    "mla_decode",
    "mla_prefill",
    "init_ffn",
    "ffn_apply",
    "init_moe",
    "moe_apply",
    "init_mamba",
    "mamba_train",
    "mamba_decode",
    "mamba_prefill",
    "init_cache_attn",
    "init_cache_mla",
    "init_cache_mamba",
]

_NEG = -1e30


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-5) -> jax.Array:
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    out = x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


# --------------------------------------------------------------------- RoPE
def rope_freqs(positions: jax.Array, dim: int, theta: float,
               yarn: YarnConfig | None = None) -> tuple[jax.Array, jax.Array]:
    """positions (...,) -> cos/sin (..., dim/2) in f32."""
    if yarn is None:
        inv = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    else:
        inv = jnp.asarray(yarn_inv_freq(dim, theta, yarn)[0])
    ang = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if yarn is not None:
        m = yarn_mscale(yarn.factor, yarn.mscale) / yarn_mscale(yarn.factor, yarn.mscale_all_dim)
        if m != 1.0:
            cos, sin = cos * m, sin * m
    return cos, sin


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature factor, 0.1 mscale ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, theta: float, yarn: YarnConfig) -> tuple[np.ndarray, int, int]:
    """YaRN's (dim/2,) inverse frequencies and its ramp's ends (low, high),
    as DeepSeek-V2's ``DeepseekV2YarnRotaryEmbedding``: dimensions that turn
    more than ``beta_fast`` times over the original context keep their
    frequency, those that turn fewer than ``beta_slow`` times are divided
    by ``factor``, and a linear ramp blends the ones between."""
    def corr(rotations):
        return (dim * math.log(yarn.original_max_positions / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(corr(yarn.beta_fast)), 0)
    high = min(math.ceil(corr(yarn.beta_slow)), dim - 1)
    expo = np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)
    extra = (1.0 / np.float32(theta) ** expo).astype(np.float32)
    inter = (1.0 / (np.float32(yarn.factor) * np.float32(theta) ** expo)).astype(np.float32)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / np.float32(high - low if high > low else 0.001), 0, 1)
    keep = (1.0 - ramp).astype(np.float32)
    return (inter * (1 - keep) + extra * keep).astype(np.float32), low, high


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x (..., L, n, dim); cos/sin (L, dim/2) broadcast over heads."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


def _dense(key, shape, dtype, scale=None):
    scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
    return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)


def _split_guard(y: jax.Array) -> jax.Array:
    """Replication barrier before splitting a fused projection.

    jnp.split at offsets that don't align with a sharded dim's tile
    boundaries is miscompiled by the SPMD partitioner (jax 0.4.37,
    verified on the CPU backend: slices crossing tile edges return
    garbage) — and sharding *back-propagation* from a downstream
    row-parallel matmul re-tiles the split input even when its weight is
    replicated. Forcing the fused tensor replicated right before the
    split keeps every slice local-and-correct; outside a mesh context
    this is a no-op. Hit by: mamba's zxbcdt in_proj and conv channel
    splits, MLA's wq (nope|rope) and w_dkv (latent|rope) splits."""
    from jax.sharding import PartitionSpec as _P

    try:
        return jax.lax.with_sharding_constraint(y, _P(*([None] * y.ndim)))
    except (ValueError, KeyError, RuntimeError, TypeError):
        return y  # no mesh in scope (single-device paths)


# ------------------------------------------------------------ GQA attention
def init_attn(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    d, h, kv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense(ks[0], (d, h * hd), dtype),
        "wk": _dense(ks[1], (d, kv * hd), dtype),
        "wv": _dense(ks[2], (d, kv * hd), dtype),
        "wo": _dense(ks[3], (h * hd, d), dtype),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((kv * hd,), dtype)
        p["bv"] = jnp.zeros((kv * hd,), dtype)
    return p


def _qkv(p: dict, x: jax.Array, cfg: ArchConfig):
    b, l, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    return (
        q.reshape(b, l, h, hd),
        k.reshape(b, l, kv, hd),
        v.reshape(b, l, kv, hd),
    )


def _sdpa(q, k, v, mask, n_rep: int, logits_bf16: bool = False):
    """q (B,Lq,H,hd), k/v (B,Lk,KV,hd); mask (B|1, 1, Lq, Lk) additive f32.

    logits_bf16 keeps the (Lq x Lk) score tensor in bf16 (with exact f32
    max-subtraction) -- the beyond-paper memory optimization; default is
    full-f32 scores (the faithful baseline)."""
    b, lq, h, hd = q.shape
    kv = k.shape[2]
    qg = q.reshape(b, lq, kv, n_rep, hd)
    if logits_bf16:
        # Fused-path variant: keep the (Lq x Lk) tensor in bf16 end-to-end
        # and let XLA fuse jax.nn.softmax (the earlier manual max/exp/div
        # split was REFUTED: +17% bytes-accessed from extra materialized ops).
        logits = jnp.einsum("bqgrh,bkgh->bgrqk", qg, k)
        logits = logits / math.sqrt(hd) + mask[:, :, None].astype(logits.dtype)
        w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    else:
        logits = jnp.einsum("bqgrh,bkgh->bgrqk", qg, k).astype(jnp.float32)
        logits = logits / math.sqrt(hd) + mask[:, :, None]
        w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bgrqk,bkgh->bqgrh", w, v)
    return out.reshape(b, lq, h, hd)


def _causal_mask(l: int, window: int) -> jax.Array:
    i = jnp.arange(l)[:, None]
    j = jnp.arange(l)[None, :]
    ok = j <= i
    if window > 0:
        ok &= (i - j) < window
    return jnp.where(ok, 0.0, _NEG)[None, None].astype(jnp.float32)  # (1,1,L,L)


def attn_train(p: dict, x: jax.Array, cfg: ArchConfig, cos, sin, causal: bool = True,
               kv_override: jax.Array | None = None) -> jax.Array:
    """Full-sequence attention. kv_override: encoder output for cross-attn."""
    b, l, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    if kv_override is None:
        q, k, v = _qkv(p, x, cfg)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
        mask = _causal_mask(l, cfg.sliding_window) if causal else jnp.zeros(
            (1, 1, l, l), jnp.float32
        )
    else:
        lk = kv_override.shape[1]
        q = (x @ p["wq"]).reshape(b, l, h, hd)
        k = (kv_override @ p["wk"]).reshape(b, lk, kv, hd)
        v = (kv_override @ p["wv"]).reshape(b, lk, kv, hd)
        mask = jnp.zeros((1, 1, l, lk), jnp.float32)
    out = _sdpa(q, k, v, mask, h // kv, logits_bf16=cfg.attn_logits_bf16)
    return out.reshape(b, l, h * hd) @ p["wo"]


def init_cache_attn(cfg: ArchConfig, batch: int, max_len: int, dtype) -> dict:
    """Ring buffer of size min(max_len, window or max_len)."""
    size = min(max_len, cfg.sliding_window) if cfg.sliding_window > 0 else max_len
    kv, hd = cfg.n_kv_heads, cfg.head_dim_
    return {
        "k": jnp.zeros((batch, size, kv, hd), dtype),
        "v": jnp.zeros((batch, size, kv, hd), dtype),
    }


def _slot_positions(pos: jax.Array, batch: int) -> jax.Array:
    """Scalar or (B,) positions -> (B,) int32 per-row positions."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = jnp.broadcast_to(pos, (batch,))
    return pos


def _ring_mask(pos: jax.Array, size: int) -> jax.Array:
    """(B,1,1,S) additive mask of written ring slots for per-row `pos`:
    absolute positions in (pos-size, pos] — all slots once wrapped,
    slot_index <= pos while filling."""
    idx = jnp.arange(size)
    written = jnp.where(pos >= size, size, pos + 1)          # (B,)
    valid = idx[None, :] < written[:, None]                  # (B,S)
    return jnp.where(valid, 0.0, _NEG)[:, None, None, :].astype(jnp.float32)


def attn_decode(p: dict, x: jax.Array, cache: dict, pos: jax.Array, cfg: ArchConfig,
                active: jax.Array | None = None) -> tuple[jax.Array, dict]:
    """One-token decode. x (B,1,d); pos scalar int32 (absolute position,
    whole batch in lockstep) or (B,) per-slot positions (the continuous-
    batching serve path, where every slot is at its own depth).

    The cache is a ring buffer of `size` slots; for full attention
    size == max_len and slot == pos. `active` (B,) bool gates the k/v
    write per row: inactive rows (free/retired serve slots) leave the
    cache untouched and their output is garbage-but-finite."""
    b, _, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    size = cache["k"].shape[1]
    pos = _slot_positions(pos, b)
    q, k, v = _qkv(p, x, cfg)
    cos, sin = rope_freqs(pos[:, None], hd, cfg.rope_theta)  # (B, 1, hd/2)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    slot = jnp.mod(pos, size)
    if active is not None:
        slot = jnp.where(active, slot, size)  # out-of-bounds => dropped
    rows = jnp.arange(b)
    ck = cache["k"].at[rows, slot].set(k[:, 0], mode="drop")
    cv = cache["v"].at[rows, slot].set(v[:, 0], mode="drop")
    mask = _ring_mask(pos, size)                             # (B,1,1,S)
    out = _sdpa(q, ck, cv, mask, h // kv, logits_bf16=cfg.attn_logits_bf16)
    y = out.reshape(b, 1, h * hd) @ p["wo"]
    return y, {"k": ck, "v": cv}


def _prefill_write_slots(tok_pos: jax.Array, n_valid: jax.Array, size: int) -> jax.Array:
    """(B,C) ring slots for a chunk write; invalid tokens (>= n_valid) go
    out of bounds so scatter-with-drop leaves their slots untouched."""
    c = tok_pos.shape[1]
    valid = jnp.arange(c)[None, :] < n_valid[:, None]
    return jnp.where(valid, jnp.mod(tok_pos, size), size)


def _prefill_mask(pos: jax.Array, n_valid: jax.Array, c: int, size: int,
                  window: int) -> jax.Array:
    """(B,1,C,S+C) additive mask for chunked prefill over the concatenated
    [pre-chunk cache snapshot | chunk keys].

    Chunk token j of row r sits at absolute position pos[r]+j. Cache slot s
    holds absolute position a_s = P - ((P - s) mod S) with P = pos-1 the
    last pre-chunk write (a_s < 0 => never written). Attending the
    *snapshot* (not the post-write cache) means within-chunk ring wraps can
    never clobber a key an earlier query still needs; with window == S at
    most one of {a_s, a_s + S} is ever inside a query's window, so the
    concatenated view never double-counts a slot. Padding queries
    (j >= n_valid) keep their own key so softmax stays finite."""
    j = jnp.arange(c)
    tok_pos = pos[:, None] + j[None, :]                      # (B,C)
    valid_tok = j[None, :] < n_valid[:, None]                # (B,C)
    # Cache snapshot part: written, and (sliding window) close enough.
    idx = jnp.arange(size)
    last = pos[:, None] - 1
    a_s = last - jnp.mod(last - idx[None, :], size)          # (B,S)
    cache_ok = jnp.broadcast_to(
        (a_s >= 0)[:, None, :], (pos.shape[0], c, size))     # (B,C,S)
    if window > 0:
        cache_ok = cache_ok & ((tok_pos[:, :, None] - a_s[:, None, :]) < window)
    # Chunk part: causal over real tokens; self-key unconditionally.
    self_k = j[None, None, :] == j[None, :, None]            # (1,C,C)
    chunk_ok = (j[None, None, :] <= j[None, :, None]) & (valid_tok[:, None, :] | self_k)
    if window > 0:
        chunk_ok = chunk_ok & ((j[None, :, None] - j[None, None, :]) < window)
    ok = jnp.concatenate(
        [cache_ok, jnp.broadcast_to(chunk_ok, (pos.shape[0], c, c))], axis=-1)
    return jnp.where(ok, 0.0, _NEG)[:, None].astype(jnp.float32)


def attn_prefill(p: dict, x: jax.Array, cache: dict, pos: jax.Array,
                 n_valid: jax.Array, cfg: ArchConfig) -> tuple[jax.Array, dict]:
    """Chunked batched prefill writing straight into the decode ring cache.

    x (B,C,d) — chunk of C tokens per row; pos (B,) absolute position of
    each row's first chunk token; n_valid (B,) real tokens in the row's
    chunk (0 => the row's cache is untouched). Queries attend the
    pre-chunk cache snapshot plus the chunk's own keys, matching
    attn_decode run token-at-a-time up to fp summation order. Requires
    C <= ring size (the serve engine clamps its chunk accordingly)."""
    b, c, d = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim_
    size = cache["k"].shape[1]
    assert c <= size, f"prefill chunk {c} exceeds ring buffer {size}"
    q, k, v = _qkv(p, x, cfg)
    tok_pos = pos[:, None] + jnp.arange(c)[None, :]          # (B,C)
    cos, sin = rope_freqs(tok_pos, hd, cfg.rope_theta)       # (B,C,hd/2)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    slot = _prefill_write_slots(tok_pos, n_valid, size)
    rows = jnp.arange(b)[:, None]
    ck = cache["k"].at[rows, slot].set(k, mode="drop")
    cv = cache["v"].at[rows, slot].set(v, mode="drop")
    mask = _prefill_mask(pos, n_valid, c, size, cfg.sliding_window)
    kk = jnp.concatenate([cache["k"], k], axis=1)            # snapshot + chunk
    vv = jnp.concatenate([cache["v"], v], axis=1)
    out = _sdpa(q, kk, vv, mask, h // kv, logits_bf16=cfg.attn_logits_bf16)
    y = out.reshape(b, c, h * hd) @ p["wo"]
    return y, {"k": ck, "v": cv}


# ------------------------------------------------------------ MLA attention
def init_mla(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    d, h = cfg.d_model, cfg.n_heads
    m = cfg.mla
    ks = jax.random.split(key, 5)
    return {
        "wq": _dense(ks[0], (d, h * (m.qk_nope_dim + m.qk_rope_dim)), dtype),
        "w_dkv": _dense(ks[1], (d, m.kv_lora_rank + m.qk_rope_dim), dtype),
        "w_uk": _dense(ks[2], (m.kv_lora_rank, h * m.qk_nope_dim), dtype),
        "w_uv": _dense(ks[3], (m.kv_lora_rank, h * m.v_head_dim), dtype),
        "wo": _dense(ks[4], (h * m.v_head_dim, d), dtype),
        "kv_norm": jnp.ones((m.kv_lora_rank,), dtype),
    }


def _mla_qkv(p, x, cfg, cos, sin):
    b, l, d = x.shape
    h, m = cfg.n_heads, cfg.mla
    q = _split_guard(x @ p["wq"]).reshape(b, l, h, m.qk_nope_dim + m.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [m.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, cos, sin)
    ckv = _split_guard(x @ p["w_dkv"])  # (b, l, lora + rope)
    c_kv, k_rope = jnp.split(ckv, [m.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, p["kv_norm"], cfg.norm_eps)
    k_rope = apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]  # shared across heads
    return q_nope, q_rope, c_kv, k_rope


def _mla_attend(p, q_nope, q_rope, c_kv, k_rope, mask, cfg):
    """Latent-space attention: absorb w_uk into q (the paper's 'weight
    absorption' trick, TPU-friendly: scores are (B,H,Lq,Lk) over the
    compressed c_kv of rank r instead of materializing full K)."""
    b, lq, h, _ = q_nope.shape
    m = cfg.mla
    w_uk = p["w_uk"].reshape(m.kv_lora_rank, h, m.qk_nope_dim)
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)  # (b,lq,h,r)
    scores = jnp.einsum("bqhr,bkr->bhqk", q_lat, c_kv)
    scores = scores + jnp.einsum("bqhn,bkn->bhqk", q_rope, k_rope)
    scale = 1.0 / math.sqrt(m.qk_nope_dim + m.qk_rope_dim)
    if cfg.yarn is not None and cfg.yarn.mscale_all_dim:
        scale *= yarn_mscale(cfg.yarn.factor, cfg.yarn.mscale_all_dim) ** 2
    logits = scores.astype(jnp.float32) * scale + mask
    w = jax.nn.softmax(logits, axis=-1).astype(c_kv.dtype)
    ctx = jnp.einsum("bhqk,bkr->bqhr", w, c_kv)  # (b,lq,h,r)
    w_uv = p["w_uv"].reshape(m.kv_lora_rank, h, m.v_head_dim)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx, w_uv)
    return out.reshape(b, lq, h * m.v_head_dim) @ p["wo"]


@jax.named_scope(scopes.MLA)
def mla_train(p: dict, x: jax.Array, cfg: ArchConfig, cos, sin) -> jax.Array:
    b, l, _ = x.shape
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cfg, cos, sin)
    mask = _causal_mask(l, cfg.sliding_window)
    return _mla_attend(p, q_nope, q_rope, c_kv, k_rope, mask[:, 0][:, None], cfg)


def init_cache_mla(cfg: ArchConfig, batch: int, max_len: int, dtype) -> dict:
    size = min(max_len, cfg.sliding_window) if cfg.sliding_window > 0 else max_len
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, size, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, size, m.qk_rope_dim), dtype),
    }


@jax.named_scope(scopes.MLA)
def mla_decode(p: dict, x: jax.Array, cache: dict, pos: jax.Array, cfg: ArchConfig,
               active: jax.Array | None = None) -> tuple[jax.Array, dict]:
    """One-token MLA decode; pos scalar or (B,) per-slot (see attn_decode)."""
    b = x.shape[0]
    size = cache["c_kv"].shape[1]
    pos = _slot_positions(pos, b)
    cos, sin = rope_freqs(pos[:, None], cfg.mla.qk_rope_dim, cfg.rope_theta, cfg.yarn)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cfg, cos, sin)
    slot = jnp.mod(pos, size)
    if active is not None:
        slot = jnp.where(active, slot, size)
    rows = jnp.arange(b)
    cc = cache["c_kv"].at[rows, slot].set(c_kv[:, 0], mode="drop")
    cr = cache["k_rope"].at[rows, slot].set(k_rope[:, 0], mode="drop")
    mask = _ring_mask(pos, size)                             # (B,1,1,S)
    y = _mla_attend(p, q_nope, q_rope, cc, cr, mask, cfg)
    return y, {"c_kv": cc, "k_rope": cr}


@jax.named_scope(scopes.MLA)
def mla_prefill(p: dict, x: jax.Array, cache: dict, pos: jax.Array,
                n_valid: jax.Array, cfg: ArchConfig) -> tuple[jax.Array, dict]:
    """Chunked MLA prefill into the compressed-KV ring cache (see
    attn_prefill for the chunk/snapshot semantics)."""
    b, c, _ = x.shape
    size = cache["c_kv"].shape[1]
    assert c <= size, f"prefill chunk {c} exceeds ring buffer {size}"
    tok_pos = pos[:, None] + jnp.arange(c)[None, :]
    cos, sin = rope_freqs(tok_pos, cfg.mla.qk_rope_dim, cfg.rope_theta, cfg.yarn)
    q_nope, q_rope, c_kv, k_rope = _mla_qkv(p, x, cfg, cos, sin)
    slot = _prefill_write_slots(tok_pos, n_valid, size)
    rows = jnp.arange(b)[:, None]
    cc = cache["c_kv"].at[rows, slot].set(c_kv, mode="drop")
    cr = cache["k_rope"].at[rows, slot].set(k_rope, mode="drop")
    mask = _prefill_mask(pos, n_valid, c, size, cfg.sliding_window)
    ckv_all = jnp.concatenate([cache["c_kv"], c_kv], axis=1)
    kr_all = jnp.concatenate([cache["k_rope"], k_rope], axis=1)
    y = _mla_attend(p, q_nope, q_rope, ckv_all, kr_all, mask, cfg)
    return y, {"c_kv": cc, "k_rope": cr}


# ------------------------------------------------------------------ SwiGLU
def init_ffn(key: jax.Array, d: int, ff: int, dtype) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "w_gate": _dense(ks[0], (d, ff), dtype),
        "w_up": _dense(ks[1], (d, ff), dtype),
        "w_down": _dense(ks[2], (ff, d), dtype),
    }


def ffn_apply(p: dict, x: jax.Array) -> jax.Array:
    return (jax.nn.silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


# --------------------------------------------------------------------- MoE
def init_moe(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    """The router over all ``n_experts``, the held experts' stacked SwiGLU
    weights, and the shared experts as one SwiGLU of ``n_shared`` widths."""
    mo = cfg.moe
    d = cfg.d_model
    de = mo.d_expert or cfg.d_ff
    ks = jax.random.split(key, 5)
    p = {
        "router": _dense(ks[0], (d, mo.n_experts), jnp.float32),  # router in f32
        "w_gate": _dense(ks[1], (mo.held, d, de), dtype),
        "w_up": _dense(ks[2], (mo.held, d, de), dtype),
        "w_down": _dense(ks[3], (mo.held, de, d), dtype),
    }
    if mo.n_shared > 0:
        p["shared"] = init_ffn(ks[4], d, mo.n_shared * de, dtype)
    return p


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _take_rows(a: jax.Array, idx: jax.Array, back: jax.Array, k: int) -> jax.Array:
    """``a[idx]``, where every row of ``a`` appears ``k`` times in ``idx``
    and ``back`` lists, for row j's copies in turn, where they sit: the
    backward pass is then a gather and a sum over the copies, with no
    scatter-add."""
    return a[idx]


def _take_rows_fwd(a, idx, back, k):
    return a[idx], back


def _take_rows_bwd(k, back, g):
    return g[back].reshape(-1, k, g.shape[-1]).sum(axis=1), None, None


_take_rows.defvjp(_take_rows_fwd, _take_rows_bwd)


GMM_TILE = (512, 512, 128)   # (rows, contraction, output) tile of the grouped matmul;
                             # fastest of those tried at DeepSeek-V2-Lite's widths (PERF.md)


def _gmm_args(m: int, dtype, mo) -> dict:
    """Tiling, input dtype and interpret mode of the grouped matmul: inputs
    in the MoE config's ``expert_dtype``, else their own dtype; interpreted
    on the CPU, as the repo's other kernels are, and compiled elsewhere."""
    return {"tiling": (min(GMM_TILE[0], m),) + GMM_TILE[1:],
            "dtype": jnp.dtype(mo.expert_dtype or dtype),
            "interpret": jax.default_backend() == "cpu"}


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _grouped_matmul(rows: jax.Array, w: jax.Array, sizes: jax.Array, mo) -> jax.Array:
    """rows (m, k), w (G, k, n), sizes (G + 1,) int32: the first G groups of
    consecutive rows times their own w[g], in f32. Tiles of the last group
    are never visited and its rows hold no result: callers mask them. A
    Pallas kernel (megablox); ``mo`` the MoE config (``_gmm_args``)."""
    return _grouped_matmul_fwd(rows, w, sizes, mo)[0]


def _grouped_matmul_fwd(rows, w, sizes, mo):
    a = _gmm_args(rows.shape[0], rows.dtype, mo)
    lhs, rhs = rows.astype(a["dtype"]), w.astype(a["dtype"])
    out = gmm(lhs, rhs, sizes, jnp.float32, a["tiling"], interpret=a["interpret"])
    return out, (lhs, rhs, sizes)


def _grouped_matmul_bwd(mo, res, g):
    lhs, rhs, sizes = res
    a = _gmm_args(lhs.shape[0], lhs.dtype, mo)
    g = g.astype(lhs.dtype)
    d_rows = gmm(g, rhs, sizes, jnp.float32, a["tiling"], transpose_rhs=True,
                 interpret=a["interpret"])
    d_w = tgmm(lhs.swapaxes(0, 1), g, sizes, jnp.float32, a["tiling"],
               num_actual_groups=rhs.shape[0], interpret=a["interpret"])
    return d_rows, d_w, None


_grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)


def _balance_loss(probs: jax.Array, idx: jax.Array, mo) -> jax.Array:
    """The router's balance loss over all experts. probs (b, l, E), idx
    (b, l, k). "switch": E * sum_e (share of top-1 choices) * (mean
    probability) over the batch. "seq" (DeepSeek-V2's sequence-wise loss):
    per sequence, each expert's count among the L*k choices over its
    uniform count L*k/E, times its mean probability, summed over experts
    and averaged over sequences."""
    e = mo.n_experts
    if mo.aux == "seq":
        b, l, k = idx.shape
        counts = jnp.sum(jax.nn.one_hot(idx.reshape(b, l * k), e, dtype=jnp.float32), axis=1)
        ce = counts / (l * k / e)
        return mo.router_aux_weight * jnp.mean(jnp.sum(ce * jnp.mean(probs, axis=1), axis=-1))
    me = jnp.mean(probs, axis=(0, 1))
    ce = jnp.mean(jax.nn.one_hot(idx[..., 0], e), axis=(0, 1))
    return e * jnp.sum(me * ce) * mo.router_aux_weight


def moe_apply(p: dict, x: jax.Array, cfg: ArchConfig) -> tuple[jax.Array, jax.Array]:
    """Dropless top-k MoE over the experts held here. x (B, L, d).

    The router scores all ``n_experts`` in f32; each token takes its top-k
    by softmax probability, with the probabilities as gates (renormalised
    to sum to 1 where ``norm_topk``). The B*L*k assignments are sorted so
    that those of the held experts come first, grouped by expert, and one
    grouped matmul per SwiGLU weight computes the held groups alone; no
    assignment is dropped. Assignments to experts held elsewhere add
    nothing here. Returns (out, aux_loss)."""
    mo = cfg.moe
    b, l, d = x.shape
    k, held, t = mo.top_k, mo.held, b * l
    tile = min(GMM_TILE[0], -(-t * k // 128) * 128)
    m = -(-t * k // tile) * tile                              # rows, whole tiles
    with jax.named_scope(scopes.MOE_ROUTE):
        probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"], axis=-1)  # (b, l, E)
        gates, idx = jax.lax.top_k(probs, k)                                 # (b, l, k)
        if mo.norm_topk:
            gates = gates / jnp.clip(gates.sum(-1, keepdims=True), 1e-9)
        aux = _balance_loss(probs, idx, mo)
    with jax.named_scope(scopes.MOE_DISPATCH):
        local = idx.reshape(t * k) - mo.expert_start
        mine = (local >= 0) & (local < held)                  # per assignment
        group = jnp.where(mine, local, held)                  # held groups first
        order = jnp.argsort(group, stable=True)               # sorted -> assignment
        back = jnp.argsort(order)                             # assignment -> sorted
        sizes = jnp.sum(jax.nn.one_hot(group, held + 1, dtype=jnp.int32), axis=0)
        sizes = sizes.at[held].add(m - t * k)                 # the rest: not computed
        kept = mine[order][:, None]
        rows = jnp.where(kept, _take_rows(x.reshape(t, d), order // k, back, k), 0)
        rows = jnp.pad(rows, ((0, m - t * k), (0, 0)))
    with jax.named_scope(scopes.MOE_EXPERTS):
        h = jax.nn.silu(_grouped_matmul(rows, p["w_gate"], sizes, mo))
        h = h * _grouped_matmul(rows, p["w_up"], sizes, mo)
        ys = _grouped_matmul(h, p["w_down"], sizes, mo)[: t * k]  # (t*k, d), sorted
    with jax.named_scope(scopes.MOE_COMBINE):
        y = jnp.where(mine[:, None], _take_rows(ys, back, order, 1), 0).reshape(t, k, d)
        out = jnp.sum(y * gates.reshape(t, k, 1).astype(y.dtype), axis=1).reshape(b, l, d)
    if "shared" in p:
        with jax.named_scope(scopes.MOE_SHARED):
            out = out + ffn_apply(p["shared"], x)
    return out, aux


# ------------------------------------------------------------------ Mamba2
def init_mamba(key: jax.Array, cfg: ArchConfig, dtype) -> dict:
    s = cfg.ssm
    d = cfg.d_model
    d_in = s.expand * d
    n_h = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.state_dim
    ks = jax.random.split(key, 4)
    return {
        "in_proj": _dense(ks[0], (d, 2 * d_in + 2 * s.n_groups * s.state_dim + n_h), dtype),
        "conv_w": _dense(ks[1], (s.d_conv, conv_dim), dtype, scale=0.5),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(jnp.linspace(1.0, 16.0, n_h)).astype(jnp.float32),
        "D": jnp.ones((n_h,), jnp.float32),
        "dt_bias": jnp.zeros((n_h,), jnp.float32),
        "norm": jnp.ones((d_in,), dtype),
        "out_proj": _dense(ks[2], (d_in, d), dtype),
    }


def _mamba_split(p, x, cfg):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_h = d_in // s.head_dim
    gn = s.n_groups * s.state_dim
    zxbcdt = _split_guard(x @ p["in_proj"])
    z, xc, bc, cc, dt = jnp.split(zxbcdt, [d_in, 2 * d_in, 2 * d_in + gn, 2 * d_in + 2 * gn], axis=-1)
    return z, xc, bc, cc, dt, n_h, d_in


def _segsum_exp(log_a: jax.Array) -> jax.Array:
    """exp(segment-sums): L[i,j] = exp(sum_{j<k<=i} log_a[k]), lower-tri.

    log_a (..., C) -> (..., C, C)."""
    c = log_a.shape[-1]
    cum = jnp.cumsum(log_a, axis=-1)
    diff = cum[..., :, None] - cum[..., None, :]  # sum_{j<k<=i}
    i = jnp.arange(c)[:, None]
    j = jnp.arange(c)[None, :]
    mask = j <= i
    # Mask BEFORE exp: exp of the (discarded) upper triangle overflows and
    # poisons the backward pass (inf * 0 = nan in the where-grad).
    diff = jnp.where(mask, diff, -jnp.inf)
    return jnp.exp(diff)


def ssd_chunked_ref(xh, dt, a_log, bb, cc, chunk: int):
    """Pure-jnp SSD (Mamba2 state-space duality, arXiv:2405.21060 Alg. 1).

    xh (B,L,H,P), dt (B,L,H) post-softplus, a_log (H,) (A = -exp(a_log)),
    bb/cc (B,L,G,N). Returns y (B,L,H,P) and final state (B,H,P,N).

    This is also the oracle for the Pallas kernel in repro/kernels/ssd_scan.
    """
    b, l, h, p = xh.shape
    g, n = bb.shape[2], bb.shape[3]
    assert l % chunk == 0, (l, chunk)
    nc = l // chunk
    rep = h // g
    a = -jnp.exp(a_log.astype(jnp.float32))             # (H,)
    dta = dt.astype(jnp.float32) * a                     # (B,L,H) log-decay
    xdt = xh * dt.astype(xh.dtype)[..., None]            # dt-weighted input

    xc = xdt.reshape(b, nc, chunk, h, p)
    dtc = dta.reshape(b, nc, chunk, h)
    bc = bb.reshape(b, nc, chunk, g, n)
    cc_ = cc.reshape(b, nc, chunk, g, n)
    bch = jnp.repeat(bc, rep, axis=3)                    # (b,nc,c,h,n)
    cch = jnp.repeat(cc_, rep, axis=3)

    # Intra-chunk (diagonal blocks): y = (C B^T ⊙ L) x
    lmat = _segsum_exp(jnp.swapaxes(dtc, -1, -2))        # (b,nc,h,c,c)
    scores = jnp.einsum("bzihn,bzjhn->bzhij", cch, bch).astype(jnp.float32)
    w = scores * lmat
    y_diag = jnp.einsum("bzhij,bzjhp->bzihp", w.astype(xh.dtype), xc)

    # Chunk-final states: S_z = sum_j decay(j->end) * B_j x_j^T
    cumsum = jnp.cumsum(dtc, axis=2)                     # (b,nc,c,h)
    decay_to_end = jnp.exp(cumsum[:, :, -1:, :] - cumsum)  # (b,nc,c,h)
    sz = jnp.einsum("bzjhn,bzjh,bzjhp->bzhpn",
                    bch, decay_to_end.astype(xh.dtype), xc)

    # Inter-chunk recurrence over z: S <- exp(sum dt a) S + S_z
    chunk_decay = jnp.exp(cumsum[:, :, -1, :])           # (b,nc,h)

    def scan_fn(s, inp):
        sz_z, dec_z = inp
        s_new = s * dec_z[..., None, None].astype(s.dtype) + sz_z
        return s_new, s

    s0 = jnp.zeros((b, h, p, n), xh.dtype)
    s_final, s_prev = jax.lax.scan(
        scan_fn,
        s0,
        (jnp.swapaxes(sz, 0, 1), jnp.swapaxes(chunk_decay, 0, 1).astype(xh.dtype)),
    )
    s_prev = jnp.swapaxes(s_prev, 0, 1)                  # (b,nc,h,p,n) state entering chunk

    # Inter-chunk contribution: y += C_i * decay(start->i) * S_prev
    decay_from_start = jnp.exp(cumsum - dtc)             # exclusive within chunk? see below
    # positions i: decay from chunk start to i inclusive of steps 1..i:
    # state seen by token i is decayed by exp(sum_{k<=i} dta_k) from chunk entry
    decay_in = jnp.exp(cumsum)                           # (b,nc,c,h)
    y_off = jnp.einsum("bzihn,bzih,bzhpn->bzihp",
                       cch, decay_in.astype(xh.dtype), s_prev)
    y = (y_diag + y_off).reshape(b, l, h, p)
    return y, s_final


def mamba_train(p: dict, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    s = cfg.ssm
    b, l, _ = x.shape
    z, xc, bc, cc, dt, n_h, d_in = _mamba_split(p, x, cfg)
    # Causal depthwise conv over (x, B, C).
    xbc = jnp.concatenate([xc, bc, cc], axis=-1)
    pad = jnp.pad(xbc, ((0, 0), (s.d_conv - 1, 0), (0, 0)))
    conv = sum(
        pad[:, i : i + l, :] * p["conv_w"][i] for i in range(s.d_conv)
    ) + p["conv_b"]
    conv = _split_guard(jax.nn.silu(conv))
    xc, bc, cc = jnp.split(conv, [d_in, d_in + s.n_groups * s.state_dim], axis=-1)
    xh = xc.reshape(b, l, n_h, s.head_dim)
    bb = bc.reshape(b, l, s.n_groups, s.state_dim)
    cv = cc.reshape(b, l, s.n_groups, s.state_dim)
    dt_ = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])
    chunk = min(s.chunk, l)
    if cfg.use_pallas_ssd:
        from repro.kernels.ssd_scan import ssd_chunked as _pallas_ssd

        y = _pallas_ssd(
            jnp.swapaxes(xh, 1, 2),                    # (B,H,L,P)
            jnp.swapaxes(dt_, 1, 2),                   # (B,H,L)
            p["A_log"],
            jnp.swapaxes(bb, 1, 2),                    # (B,G,L,N)
            jnp.swapaxes(cv, 1, 2),
            chunk=chunk,
            interpret=jax.default_backend() == "cpu",
        )
        y = jnp.swapaxes(y, 1, 2)                      # back to (B,L,H,P)
    else:
        y, _ = ssd_chunked_ref(xh, dt_, p["A_log"], bb, cv, chunk)
    y = y + xh * p["D"].astype(xh.dtype)[:, None]
    y = y.reshape(b, l, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    return y @ p["out_proj"]


def init_cache_mamba(cfg: ArchConfig, batch: int, dtype) -> dict:
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_h = d_in // s.head_dim
    conv_dim = d_in + 2 * s.n_groups * s.state_dim
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, n_h, s.head_dim, s.state_dim), dtype),
    }


def mamba_decode(p: dict, x: jax.Array, cache: dict, cfg: ArchConfig,
                 active: jax.Array | None = None) -> tuple[jax.Array, dict]:
    """O(1) recurrent step. x (B,1,d). `active` (B,) bool gates the
    conv/ssm state advance per row (inactive serve slots stay frozen)."""
    s = cfg.ssm
    b = x.shape[0]
    z, xc, bc, cc, dt, n_h, d_in = _mamba_split(p, x, cfg)
    xbc = jnp.concatenate([xc, bc, cc], axis=-1)         # (b,1,conv_dim)
    window = jnp.concatenate([cache["conv"], xbc], axis=1)  # (b,d_conv,conv_dim)
    conv = jnp.einsum("btc,tc->bc", window, p["conv_w"]) + p["conv_b"]
    conv = _split_guard(jax.nn.silu(conv)[:, None, :])
    new_conv_cache = window[:, 1:, :]
    xc, bc, cc = jnp.split(conv, [d_in, d_in + s.n_groups * s.state_dim], axis=-1)
    xh = xc.reshape(b, n_h, s.head_dim)
    bb = bc.reshape(b, s.n_groups, s.state_dim)
    cv = cc.reshape(b, s.n_groups, s.state_dim)
    rep = n_h // s.n_groups
    bbh = jnp.repeat(bb, rep, axis=1)                    # (b,h,n)
    cvh = jnp.repeat(cv, rep, axis=1)
    dt_ = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"])[:, 0]  # (b,h)
    a = -jnp.exp(p["A_log"].astype(jnp.float32))
    alpha = jnp.exp(dt_ * a)                             # (b,h)
    st = cache["ssm"]
    st = st * alpha[..., None, None].astype(st.dtype) + jnp.einsum(
        "bhp,bhn->bhpn", xh * dt_.astype(xh.dtype)[..., None], bbh
    )
    y = jnp.einsum("bhpn,bhn->bhp", st, cvh) + xh * p["D"].astype(xh.dtype)[:, None]
    y = y.reshape(b, 1, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["norm"], cfg.norm_eps)
    if active is not None:
        new_conv_cache = jnp.where(active[:, None, None], new_conv_cache, cache["conv"])
        st = jnp.where(active[:, None, None, None], st, cache["ssm"])
    return y @ p["out_proj"], {"conv": new_conv_cache, "ssm": st}


def mamba_prefill(p: dict, x: jax.Array, cache: dict, n_valid: jax.Array,
                  cfg: ArchConfig) -> tuple[jax.Array, dict]:
    """Chunked prefill for the recurrent mixer: scans the O(1) decode step
    over the chunk inside one program, gating the conv/ssm state advance
    per token so rows with different n_valid advance exactly that many
    steps — bit-identical to mamba_decode run token-at-a-time. (The SSD
    chunk-parallel formulation is the TPU production variant; at serve
    chunk sizes the recurrence is one fused scan and not the bottleneck —
    attention prefill is.)"""
    b, c, _ = x.shape

    def body(carry, inp):
        xt, t = inp
        y, nc = mamba_decode(p, xt, carry, cfg, active=t < n_valid)
        return nc, y[:, 0]

    xs = (jnp.moveaxis(x, 0, 1)[:, :, None, :], jnp.arange(c))
    new_cache, ys = jax.lax.scan(body, cache, xs)
    return jnp.moveaxis(ys, 0, 1), new_cache
