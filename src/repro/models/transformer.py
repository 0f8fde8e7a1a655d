"""Unified decoder/encoder-decoder LM over `ArchConfig`.

One implementation covers all ten assigned architectures:
- layer *blocks* (cfg.block_pattern) are scanned with stacked params, so an
  80-layer model lowers as a single rolled loop (fast multi-arch dry-runs);
  ``cfg.n_dense_layers`` leading layers (attention + a SwiGLU of width
  d_ff, DeepSeek's first dense layers) run first, as a scan of their own;
- each block slot is attn (GQA or MLA) or mamba (SSD), with dense or MoE FFN;
- enc-dec (seamless) adds a scanned bidirectional encoder + cross-attention;
- VLM/audio frontends are stubs per the brief: the caller supplies
  precomputed patch/frame embeddings which are prepended (VLM) or encoded
  (audio enc-dec).

Public API: init_params / abstract_params / forward_train / loss_fn /
init_cache / decode_step.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models.config import ArchConfig
from repro.models import layers as L

__all__ = [
    "init_params",
    "abstract_params",
    "forward_train",
    "loss_fn",
    "init_cache",
    "decode_step",
    "prefill_chunk",
]


# ----------------------------------------------------------------- builders
def _init_slot(key, cfg: ArchConfig, slot: int, dtype) -> dict:
    kind = cfg.block_pattern[slot]
    k1, k2 = jax.random.split(key)
    p: dict = {"norm1": jnp.ones((cfg.d_model,), dtype), "norm2": jnp.ones((cfg.d_model,), dtype)}
    if kind == "attn":
        p["mixer"] = (
            L.init_mla(k1, cfg, dtype) if cfg.attn_type == "mla" else L.init_attn(k1, cfg, dtype)
        )
    else:
        p["mixer"] = L.init_mamba(k1, cfg, dtype)
    fk = cfg.ffn_kind(slot)
    if fk == "moe":
        p["ffn"] = L.init_moe(k2, cfg, dtype)
    elif fk == "dense":
        p["ffn"] = L.init_ffn(k2, cfg.d_model, cfg.d_ff, dtype)
    else:  # "none" (e.g. mamba2: the mixer IS the layer)
        del p["norm2"]
    return p


def _dense_cfg(cfg: ArchConfig) -> ArchConfig:
    """The leading dense layers as a one-slot pattern: attention, SwiGLU."""
    return dataclasses.replace(cfg, block_pattern=("attn",), ffn_pattern=("dense",))


def _stack(trees: list) -> Any:
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


def _init_block(key, cfg: ArchConfig, dtype) -> dict:
    keys = jax.random.split(key, len(cfg.block_pattern))
    return {f"slot{i}": _init_slot(keys[i], cfg, i, dtype) for i in range(len(cfg.block_pattern))}


def _init_enc_layer(key, cfg: ArchConfig, dtype) -> dict:
    k1, k2 = jax.random.split(key)
    return {
        "norm1": jnp.ones((cfg.d_model,), dtype),
        "norm2": jnp.ones((cfg.d_model,), dtype),
        "mixer": L.init_attn(k1, cfg, dtype),
        "ffn": L.init_ffn(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def _init_cross_layer(key, cfg: ArchConfig, dtype) -> dict:
    return {"norm": jnp.ones((cfg.d_model,), dtype), "mixer": L.init_attn(key, cfg, dtype)}


def init_params(cfg: ArchConfig, key: jax.Array, dtype=jnp.bfloat16) -> dict:
    """Concrete init. Blocks are stacked along a leading n_blocks dim."""
    kb, ke, kh, kenc, kx = jax.random.split(key, 5)
    block_keys = jax.random.split(kb, cfg.n_blocks)
    blocks = [_init_block(block_keys[i], cfg, dtype) for i in range(cfg.n_blocks)]
    params = {
        "embed": (0.02 * jax.random.normal(ke, (cfg.vocab, cfg.d_model), jnp.float32)).astype(dtype),
        "final_norm": jnp.ones((cfg.d_model,), dtype),
        "blocks": _stack(blocks),
    }
    if cfg.n_dense_layers:
        dense_keys = jax.random.split(jax.random.fold_in(key, 5), cfg.n_dense_layers)
        params["dense"] = _stack([_init_slot(k, _dense_cfg(cfg), 0, dtype) for k in dense_keys])
    if not cfg.tie_embeddings:
        params["head"] = (
            0.02 * jax.random.normal(kh, (cfg.d_model, cfg.vocab), jnp.float32)
        ).astype(dtype)
    if cfg.enc_dec:
        enc_keys = jax.random.split(kenc, cfg.n_enc_layers)
        params["encoder"] = _stack([_init_enc_layer(k, cfg, dtype) for k in enc_keys])
        x_keys = jax.random.split(kx, cfg.n_blocks)
        params["cross"] = _stack([_init_cross_layer(k, cfg, dtype) for k in x_keys])
        params["enc_norm"] = jnp.ones((cfg.d_model,), dtype)
    return params


def abstract_params(cfg: ArchConfig, dtype=jnp.bfloat16) -> Any:
    """ShapeDtypeStruct pytree (no allocation) for .lower() dry-runs."""
    return jax.eval_shape(lambda k: init_params(cfg, k, dtype), jax.random.PRNGKey(0))


# ------------------------------------------------------------------ forward
def _bp_constraint(h: jax.Array, axes=("data", "model")):
    """Batch-parallel attention region: activations sharded over `axes` on
    the batch dim (no tensor parallelism inside attention; XLA inserts the
    boundary reshards). `axes` shrinks to ("data",) for shapes whose batch
    does not divide data*model (uneven GSPMD padding costs compute). Only
    active under a mesh that has the axes (the dry-run/production path)."""
    from jax.sharding import PartitionSpec as _P

    try:
        spec = tuple(axes) if len(axes) > 1 else axes[0]
        return jax.lax.with_sharding_constraint(
            h, _P(spec, *([None] * (h.ndim - 1)))
        )
    except (ValueError, KeyError, RuntimeError, TypeError):
        return h  # host mesh without those axes


def _apply_slot(p: dict, x: jax.Array, cfg: ArchConfig, slot: int, cos, sin):
    kind = cfg.block_pattern[slot]
    h = L.rms_norm(x, p["norm1"], cfg.norm_eps)
    if kind == "attn":
        if cfg.attn_batch_parallel:
            h = _bp_constraint(h, cfg.attn_bp_axes)
        if cfg.attn_type == "mla":
            h = L.mla_train(p["mixer"], h, cfg, cos, sin)
        else:
            h = L.attn_train(p["mixer"], h, cfg, cos, sin)
        if cfg.attn_batch_parallel:
            h = _bp_constraint(h, cfg.attn_bp_axes)
    else:
        h = L.mamba_train(p["mixer"], h, cfg)
    x = x + h
    fk = cfg.ffn_kind(slot)
    if fk == "none":
        return x, jnp.zeros((), jnp.float32)
    h = L.rms_norm(x, p["norm2"], cfg.norm_eps)
    if fk == "moe":
        h, aux = L.moe_apply(p["ffn"], h, cfg)
    else:
        h, aux = L.ffn_apply(p["ffn"], h), jnp.zeros((), jnp.float32)
    return x + h, aux


def _block_fn(cfg: ArchConfig, x, bp, cos, sin):
    aux_total = jnp.zeros((), jnp.float32)
    for i in range(len(cfg.block_pattern)):
        x, aux = _apply_slot(bp[f"slot{i}"], x, cfg, i, cos, sin)
        aux_total = aux_total + aux
    return x, aux_total


def _run_blocks(cfg: ArchConfig, params: dict, x: jax.Array, cos, sin,
                enc_out: jax.Array | None = None, remat: bool = True,
                unroll: bool = False):
    def body(carry, bp_and_cross):
        h = carry
        if cfg.enc_dec:
            bp, cp = bp_and_cross
        else:
            bp, cp = bp_and_cross, None
        h, aux = _block_fn(cfg, h, bp, cos, sin)
        if cp is not None:
            hn = L.rms_norm(h, cp["norm"], cfg.norm_eps)
            h = h + L.attn_train(cp["mixer"], hn, cfg, cos, sin, kv_override=enc_out)
        return h, aux

    body_fn = jax.checkpoint(body) if remat else body
    xs = (params["blocks"], params["cross"]) if cfg.enc_dec else params["blocks"]
    x, auxs = jax.lax.scan(body_fn, x, xs, unroll=True if unroll else 1)
    return x, jnp.sum(auxs)


def _run_dense(cfg: ArchConfig, params: dict, x: jax.Array, cos, sin, remat: bool = True,
               unroll: bool = False):
    """The leading dense layers, scanned over their stack."""
    dcfg = _dense_cfg(cfg)

    def body(h, lp):
        return _apply_slot(lp, h, dcfg, 0, cos, sin)[0], None

    body_fn = jax.checkpoint(body) if remat else body
    x, _ = jax.lax.scan(body_fn, x, params["dense"], unroll=True if unroll else 1)
    return x


def _run_encoder(cfg: ArchConfig, params: dict, embeds: jax.Array, remat: bool = True,
                 unroll: bool = False):
    l = embeds.shape[1]
    cos, sin = L.rope_freqs(jnp.arange(l), cfg.head_dim_, cfg.rope_theta)

    def body(h, lp):
        hn = L.rms_norm(h, lp["norm1"], cfg.norm_eps)
        h = h + L.attn_train(lp["mixer"], hn, cfg, cos, sin, causal=False)
        hn = L.rms_norm(h, lp["norm2"], cfg.norm_eps)
        h = h + L.ffn_apply(lp["ffn"], hn)
        return h, None

    body_fn = jax.checkpoint(body) if remat else body
    h, _ = jax.lax.scan(body_fn, embeds, params["encoder"], unroll=True if unroll else 1)
    return L.rms_norm(h, params["enc_norm"], cfg.norm_eps)


def forward_train(cfg: ArchConfig, params: dict, tokens: jax.Array,
                  frontend_embeds: jax.Array | None = None, remat: bool = True,
                  unroll: bool = False):
    """tokens (B, S_text). frontend_embeds (B, F, d) for vlm/audio stubs.

    Returns (logits over text positions, aux_loss)."""
    dtype = params["embed"].dtype
    x = params["embed"][tokens].astype(dtype)
    enc_out = None
    n_front = 0
    if cfg.enc_dec:
        assert frontend_embeds is not None, "enc-dec needs encoder embeddings"
        enc_out = _run_encoder(cfg, params, frontend_embeds.astype(dtype), remat, unroll)
    elif frontend_embeds is not None:
        n_front = frontend_embeds.shape[1]
        x = jnp.concatenate([frontend_embeds.astype(dtype), x], axis=1)
    l = x.shape[1]
    cos, sin = L.rope_freqs(jnp.arange(l), cfg.rope_dim, cfg.rope_theta, cfg.yarn)
    if cfg.n_dense_layers:
        x = _run_dense(cfg, params, x, cos, sin, remat, unroll)
    x, aux = _run_blocks(cfg, params, x, cos, sin, enc_out=enc_out, remat=remat, unroll=unroll)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if n_front > 0:
        x = x[:, n_front:, :]
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = x @ head
    return logits, aux


def loss_fn(cfg: ArchConfig, params: dict, batch: dict, remat: bool = True,
            unroll: bool = False) -> jax.Array:
    """batch: {"tokens": (B,S), "labels": (B,S), optional "embeds": (B,F,d)}."""
    logits, aux = forward_train(
        cfg, params, batch["tokens"], batch.get("embeds"), remat=remat, unroll=unroll
    )
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    labels = batch["labels"]
    nll = -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return jnp.mean(nll) + aux


# ------------------------------------------------------------------- decode
def _init_cache_slot(cfg: ArchConfig, slot: int, batch: int, max_len: int, dtype) -> dict:
    kind = cfg.block_pattern[slot]
    if kind == "attn":
        if cfg.attn_type == "mla":
            return L.init_cache_mla(cfg, batch, max_len, dtype)
        return L.init_cache_attn(cfg, batch, max_len, dtype)
    return L.init_cache_mamba(cfg, batch, dtype)


def init_cache(cfg: ArchConfig, batch: int, max_len: int, dtype=jnp.bfloat16,
               enc_len: int = 0) -> dict:
    """Stacked (n_blocks-leading) cache pytree; the leading dense layers'
    caches stack under "dense"; enc-dec additionally caches the encoder
    output for cross-attention."""
    def stack(make, n=cfg.n_blocks):
        one = make()
        return jax.tree_util.tree_map(
            lambda leaf: jnp.broadcast_to(leaf, (n, *leaf.shape)).copy(), one
        )

    cache = {
        "slots": {
            f"slot{i}": stack(functools.partial(_init_cache_slot, cfg, i, batch, max_len, dtype))
            for i in range(len(cfg.block_pattern))
        },
        "pos": jnp.zeros((), jnp.int32),
    }
    if cfg.n_dense_layers:
        cache["dense"] = stack(functools.partial(_init_cache_slot, _dense_cfg(cfg), 0, batch,
                                                 max_len, dtype), cfg.n_dense_layers)
    if cfg.enc_dec:
        cache["enc_out"] = jnp.zeros((batch, enc_len or cfg.frontend_tokens, cfg.d_model), dtype)
    return cache


def _ffn_residual(cfg: ArchConfig, slot: int, p: dict, h: jax.Array) -> jax.Array:
    """The slot's FFN (dense or MoE) and its residual, as the serve paths
    run it (no balance loss)."""
    fk = cfg.ffn_kind(slot)
    if fk == "none":
        return h
    hn = L.rms_norm(h, p["norm2"], cfg.norm_eps)
    if fk == "moe":
        return h + L.moe_apply(p["ffn"], hn, cfg)[0]
    return h + L.ffn_apply(p["ffn"], hn)


def _decode_slot(cfg: ArchConfig, slot: int, p: dict, h: jax.Array, c: dict, pos,
                 active) -> tuple[jax.Array, dict]:
    hn = L.rms_norm(h, p["norm1"], cfg.norm_eps)
    if cfg.block_pattern[slot] == "attn":
        if cfg.attn_type == "mla":
            out, nc = L.mla_decode(p["mixer"], hn, c, pos, cfg, active=active)
        else:
            out, nc = L.attn_decode(p["mixer"], hn, c, pos, cfg, active=active)
    else:
        out, nc = L.mamba_decode(p["mixer"], hn, c, cfg, active=active)
    return _ffn_residual(cfg, slot, p, h + out), nc


def _prefill_slot(cfg: ArchConfig, slot: int, p: dict, h: jax.Array, c: dict, positions,
                  n_valid) -> tuple[jax.Array, dict]:
    hn = L.rms_norm(h, p["norm1"], cfg.norm_eps)
    if cfg.block_pattern[slot] == "attn":
        if cfg.attn_type == "mla":
            out, nc = L.mla_prefill(p["mixer"], hn, c, positions, n_valid, cfg)
        else:
            out, nc = L.attn_prefill(p["mixer"], hn, c, positions, n_valid, cfg)
    else:
        out, nc = L.mamba_prefill(p["mixer"], hn, c, n_valid, cfg)
    return _ffn_residual(cfg, slot, p, h + out), nc


def _serve_layers(cfg: ArchConfig, params: dict, cache: dict, x: jax.Array, slot_fn,
                  unroll: bool):
    """The leading dense layers, then the scanned blocks, each slot through
    ``slot_fn(cfg, slot, params, h, cache) -> (h, new cache)``, and the
    enc-dec cross-attention (NoPE over the cached encoder output); returns
    the final-normed hidden state and the new cache."""
    unroll = True if unroll else 1
    new_cache = dict(cache)
    if cfg.n_dense_layers:
        dcfg = _dense_cfg(cfg)

        def dense_body(h, scanned):
            p, c = scanned
            return slot_fn(dcfg, 0, p, h, c)

        x, new_cache["dense"] = jax.lax.scan(dense_body, x, (params["dense"], cache["dense"]),
                                             unroll=unroll)

    def body(h, scanned):
        if cfg.enc_dec:
            bp, cp, bc = scanned
        else:
            (bp, bc), cp = scanned, None
        new_bc = {}
        for i in range(len(cfg.block_pattern)):
            h, new_bc[f"slot{i}"] = slot_fn(cfg, i, bp[f"slot{i}"], h, bc[f"slot{i}"])
        if cp is not None:
            hn = L.rms_norm(h, cp["norm"], cfg.norm_eps)
            h = h + L.attn_train(cp["mixer"], hn, cfg, None, None, kv_override=cache["enc_out"])
        return h, new_bc

    if cfg.enc_dec:
        xs = (params["blocks"], params["cross"], cache["slots"])
    else:
        xs = (params["blocks"], cache["slots"])
    x, new_cache["slots"] = jax.lax.scan(body, x, xs, unroll=unroll)
    return L.rms_norm(x, params["final_norm"], cfg.norm_eps), new_cache


def decode_step(cfg: ArchConfig, params: dict, cache: dict, token: jax.Array,
                unroll: bool = False, positions: jax.Array | None = None,
                active: jax.Array | None = None):
    """token (B, 1) int32 -> (logits (B, 1, V), new cache). serve_step body.

    Legacy lockstep mode (positions=None): every row is at cache["pos"],
    which advances by one. Slot mode (the continuous-batching serve path):
    ``positions`` (B,) gives each row its own absolute position and
    ``active`` (B,) bool freezes the cache of free/retired slots; the
    caller owns position tracking and cache["pos"] is left untouched."""
    dtype = params["embed"].dtype
    x = params["embed"][token].astype(dtype)
    pos = cache["pos"] if positions is None else positions
    x, new_cache = _serve_layers(
        cfg, params, cache, x,
        lambda c_, i, p, h, c: _decode_slot(c_, i, p, h, c, pos, active), unroll)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    logits = x @ head
    if positions is None:
        new_cache["pos"] = pos + 1
    return logits, new_cache


def prefill_chunk(cfg: ArchConfig, params: dict, cache: dict, tokens: jax.Array,
                  positions: jax.Array, n_valid: jax.Array, unroll: bool = False):
    """Chunked batched prefill writing straight into the decode cache.

    tokens (B, C) int32 — the next chunk of each slot's prompt, right-
    padded; positions (B,) absolute position of each row's first chunk
    token; n_valid (B,) real tokens per row (0 => the row — a decoding or
    free slot — is untouched). Returns (logits (B, C, V), new cache);
    logits at j >= n_valid[r] are garbage-but-finite, and cache["pos"] is
    never consulted (per-slot positions are the caller's). Replaces the
    token-at-a-time prefill loop: one call advances every prefilling slot
    by up to C tokens, sharing the decode-path cache layout and numerics
    (attention sums differ only in fp reduction order; the recurrent
    mixer is bit-identical; the dropless MoE routes every token alone)."""
    dtype = params["embed"].dtype
    x = params["embed"][tokens].astype(dtype)
    x, new_cache = _serve_layers(
        cfg, params, cache, x,
        lambda c_, i, p, h, c: _prefill_slot(c_, i, p, h, c, positions, n_valid), unroll)
    head = params["embed"].T if cfg.tie_embeddings else params["head"]
    return x @ head, new_cache
