"""Plain ``jax.numpy`` models, their seeded initial weights and their flat
layout, written from the published descriptions and imported from nothing
of the program.

* 2FNN (arXiv:2508.21286 §VI-A): 784-100-10, ReLU hidden, softmax output.
* LSTM LM (§VI-F): embedding, stacked LSTM cells, a dense layer over the
  vocabulary, loss on the last position's next token. The cell adds 1 to
  the forget-gate pre-activation (the usual forget-bias initialisation),
  as the program's cell does.
* Llama-style decoder (Yi-6B, arXiv:2403.04652): RMSNorm, rotary GQA
  attention with a causal mask, SwiGLU MLP, untied LM head, mean
  next-token cross entropy.

The initial weights are made here, from the run's seed, and handed both to
the program and to the reference: N(0, 1) scaled by 1/sqrt(fan in) (He for
the FNN), 0.1 N(0, 1) for the LSTM's embedding and output layer, 0.02
N(0, 1) for the decoder's embedding and head, ones for norm scales, zeros
for biases.

``layout`` gives the flat buffer the protocol engine keeps its n client
models in: leaves in pytree order, each padded to a multiple of 128 lanes.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

LANES = 128

__all__ = ["LANES", "abstract", "init", "loss", "layout", "flatten"]


# ------------------------------------------------------------------ shapes
def _fnn_shapes(m: dict) -> list:
    dims = m["dims"]
    return [((dims[i], dims[i + 1]), (dims[i + 1],)) for i in range(len(dims) - 1)]


def _lstm_shapes(m: dict) -> dict:
    h, e, v = m["hidden"], m["embed"], m["vocab"]
    cells, d_in = [], e
    for _ in range(m["layers"]):
        cells.append(((d_in, 4 * h), (h, 4 * h), (4 * h,)))
        d_in = h
    return {"cells": cells, "embed": (v, e), "out_b": (v,), "out_w": (h, v)}


def _decoder_shapes(m: dict) -> dict:
    d, ff, v, n = m["hidden_size"], m["intermediate_size"], m["vocab_size"], m["num_hidden_layers"]
    h, kv = m["num_attention_heads"], m["num_key_value_heads"]
    hd = d // h
    slot = {"ffn": {"w_down": (n, ff, d), "w_gate": (n, d, ff), "w_up": (n, d, ff)},
            "mixer": {"wk": (n, d, kv * hd), "wo": (n, h * hd, d),
                      "wq": (n, d, h * hd), "wv": (n, d, kv * hd)},
            "norm1": (n, d), "norm2": (n, d)}
    return {"blocks": {"slot0": slot}, "embed": (v, d), "final_norm": (d,),
            "head": (d, v)}


_SHAPES = {"fnn": _fnn_shapes, "lstm": _lstm_shapes, "decoder": _decoder_shapes}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def abstract(m: dict, dtype=jnp.float32):
    """ShapeDtypeStruct pytree of one model of description ``m``."""
    return jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(s, dtype),
                                  _SHAPES[m["kind"]](m), is_leaf=_is_shape)


# -------------------------------------------------------------------- init
def _leaf_init(m: dict, path: str, shape: tuple, key, dtype):
    kind = m["kind"]
    z = jax.random.normal(key, shape, jnp.float32)
    if kind == "fnn":
        out = z * math.sqrt(2.0 / shape[0]) if len(shape) == 2 else jnp.zeros(shape)
    elif kind == "lstm":
        if "embed" in path or "out_w" in path:
            out = 0.1 * z
        elif len(shape) == 2:
            out = z * math.sqrt(1.0 / shape[0])
        else:
            out = jnp.zeros(shape)
    else:
        if "norm" in path:
            out = jnp.ones(shape)
        elif "embed" in path or "head" in path:
            out = 0.02 * z
        else:
            out = z / math.sqrt(shape[-2])
    return out.astype(dtype)


def init(m: dict, key, dtype=jnp.float32):
    """One model's weights from ``key``; traceable, so callers jit it."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(abstract(m))
    keys = jax.random.split(key, len(paths))
    leaves = [_leaf_init(m, jax.tree_util.keystr(p), a.shape, k, dtype)
              for (p, a), k in zip(paths, keys)]
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ------------------------------------------------------------------ layout
def layout(m: dict) -> dict:
    """Flat layout of one model: per-leaf sizes, padded sizes, offsets."""
    sizes = [int(np.prod(a.shape)) for a in jax.tree_util.tree_leaves(abstract(m))]
    padded = [-(-s // LANES) * LANES for s in sizes]
    offsets = [int(o) for o in np.concatenate([[0], np.cumsum(padded)[:-1]])]
    return {"sizes": sizes, "padded": padded, "offsets": offsets,
            "d": int(sum(sizes)), "d_pad": int(sum(padded))}


def flatten(params, lay: dict):
    """One model's pytree -> its (d_pad,) flat vector (zero padding)."""
    segs = [jnp.pad(leaf.reshape(-1), (0, p - s))
            for leaf, s, p in zip(jax.tree_util.tree_leaves(params),
                                  lay["sizes"], lay["padded"])]
    return jnp.concatenate(segs)


# -------------------------------------------------------------------- loss
def _fnn_loss(params, batch):
    x, y = batch
    h = x.reshape(x.shape[0], -1).astype(params[0][0].dtype)
    for i, (w, b) in enumerate(params):
        h = h @ w + b
        if i < len(params) - 1:
            h = jnp.maximum(h, 0)
    logp = jax.nn.log_softmax(h.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))


def _lstm_loss(params, batch):
    tokens, target = batch
    x = params["embed"][tokens]                                  # (B, T, E)
    for wx, wh, b in params["cells"]:
        hid = wh.shape[0]
        h = jnp.zeros((x.shape[0], hid), x.dtype)
        c = jnp.zeros((x.shape[0], hid), x.dtype)
        outs = []
        for t in range(x.shape[1]):
            z = x[:, t] @ wx + h @ wh + b
            i, f, g, o = jnp.split(z, 4, axis=-1)
            c = jax.nn.sigmoid(f + 1.0) * c + jax.nn.sigmoid(i) * jnp.tanh(g)
            h = jax.nn.sigmoid(o) * jnp.tanh(c)
            outs.append(h)
        x = jnp.stack(outs, axis=1)
    logits = x[:, -1] @ params["out_w"] + params["out_b"]
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, target[:, None], axis=-1))


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) * 2 / x.shape[-1])
    ang = pos[:, None].astype(jnp.float32) * inv                # (L, half)
    cos = jnp.cos(ang)[:, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _decoder_loss(params, batch, m):
    tokens, labels = batch["tokens"], batch["labels"]
    d, nh, kv = m["hidden_size"], m["num_attention_heads"], m["num_key_value_heads"]
    hd, eps, theta = d // nh, m["rms_norm_eps"], m["rope_theta"]
    b, l = tokens.shape
    pos = jnp.arange(l)
    causal = jnp.tril(jnp.ones((l, l), bool))
    x = params["embed"][tokens]
    blocks = params["blocks"]["slot0"]
    for i in range(m["num_hidden_layers"]):
        p = jax.tree_util.tree_map(lambda a: a[i], blocks)
        h = _rms(x, p["norm1"], eps)
        q = _rope((h @ p["mixer"]["wq"]).reshape(b, l, nh, hd), pos, theta)
        k = _rope((h @ p["mixer"]["wk"]).reshape(b, l, kv, hd), pos, theta)
        v = (h @ p["mixer"]["wv"]).reshape(b, l, kv, hd)
        k = jnp.repeat(k, nh // kv, axis=2)                     # head j reads kv j // rep
        v = jnp.repeat(v, nh // kv, axis=2)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
        s = jnp.where(causal, s.astype(jnp.float32), -jnp.inf)
        w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        att = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, l, nh * hd)
        x = x + att @ p["mixer"]["wo"]
        h = _rms(x, p["norm2"], eps)
        f = p["ffn"]
        x = x + (jax.nn.silu(h @ f["w_gate"]) * (h @ f["w_up"])) @ f["w_down"]
    x = _rms(x, params["final_norm"], eps)
    logp = jax.nn.log_softmax((x @ params["head"]).astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], axis=-1))


def loss(m: dict):
    """The loss function ``(params, batch) -> scalar`` of description ``m``."""
    if m["kind"] == "fnn":
        return _fnn_loss
    if m["kind"] == "lstm":
        return _lstm_loss
    return lambda p, b: _decoder_loss(p, b, m)
