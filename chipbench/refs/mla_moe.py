"""Plain reference of DeepSeek-V2-Lite's decoder at one chip's share of its
experts (arXiv:2405.04434; HF ``deepseek-ai/DeepSeek-V2-Lite``), written
from the published description and imported from nothing of the program.

* Layers: RMSNorm, multi-head latent attention, RMSNorm, then a SwiGLU of
  width ``intermediate_size`` for the first ``first_k_dense_replace``
  layers and a routed-expert layer for the rest; a final RMSNorm and an
  untied head over the vocabulary slice.
* MLA without q LoRA: q = h Wq, split into (nope 128 | rope 64) per head;
  [c | k_rope] = h Wdkv; c = RMSNorm(c); k_nope = c Wuk, v = c Wuv per head
  (the unabsorbed form: HF's kv_b_proj is [Wuk | Wuv]); k_rope is one head
  shared by all; scores over the whole 192 dimensions with a causal mask,
  scaled by 1/sqrt(192) times YaRN's mscale(40, 0.707)^2.
* YaRN rope (``DeepseekV2YarnRotaryEmbedding``): frequencies blended from
  the original and the factor-divided ones by a linear ramp between the
  dimensions that turn ``beta_fast`` and ``beta_slow`` times over the
  original context; the rotation turns the two halves of the rope
  dimensions (HF de-interleaves them first: a fixed permutation of the
  rope columns under random weights).
* Routed experts: softmax over the router's 64 outputs in float32, greedy
  top-6, the probabilities as gates with no renormalisation; the output
  is the gated sum of the held experts' SwiGLUs (a loop over the held
  experts, each over every token, masked by its gates), plus the shared
  experts' SwiGLU of width 2 x 1408. Experts held elsewhere add nothing.
* Loss: mean next-token cross entropy over the slice, plus each MoE
  layer's sequence-wise balance loss over all 64 experts,
  alpha * mean_b sum_e (count of e in sequence b's L*k choices / (L*k/E))
  * mean_l p[b, l, e].

Initial weights: 0.02 N(0, 1) for the embedding and the head, ones for
norm scales, N(0, 1) / sqrt(fan in) elsewhere; the tree is the one the
program's parameters take.

``run`` replays the pod step's momentum SGD over the checked batches, as
``refs/fedstep_ref.py`` does for the Llama-style decoder, each layer
recomputed in the backward pass so that the (B, H, L, L) scores of one
layer at a time are held, and the layers of a kind looped over their
stack (one compiled body each).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["shapes", "init", "yarn_inv_freq", "mscale", "forward", "loss", "step_program", "run"]


# ------------------------------------------------------------------ shapes
def _mla_shapes(m: dict, n: int) -> dict:
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, vd, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
                         m["kv_lora_rank"])
    return {"kv_norm": (n, r), "w_dkv": (n, d, r + rope), "w_uk": (n, r, h * nope),
            "w_uv": (n, r, h * vd), "wo": (n, h * vd, d), "wq": (n, d, h * (nope + rope))}


def shapes(m: dict) -> dict:
    """The parameter tree's shapes: the dense layers stacked under "dense",
    the MoE layers under "blocks"/"slot0"."""
    d, v = m["hidden_size"], m["vocab_size"]
    nd = m["first_k_dense_replace"]
    n = m["num_hidden_layers"] - nd
    ff, fe, held = m["intermediate_size"], m["moe_intermediate_size"], m["n_routed_experts"]
    fs = m["n_shared_experts"] * fe
    moe = {"router": (n, d, m["router_experts"]),
           "shared": {"w_down": (n, fs, d), "w_gate": (n, d, fs), "w_up": (n, d, fs)},
           "w_down": (n, held, fe, d), "w_gate": (n, held, d, fe), "w_up": (n, held, d, fe)}
    dense = {"w_down": (nd, ff, d), "w_gate": (nd, d, ff), "w_up": (nd, d, ff)}
    return {"blocks": {"slot0": {"ffn": moe, "mixer": _mla_shapes(m, n),
                                 "norm1": (n, d), "norm2": (n, d)}},
            "dense": {"ffn": dense, "mixer": _mla_shapes(m, nd), "norm1": (nd, d),
                      "norm2": (nd, d)},
            "embed": (v, d), "final_norm": (d,), "head": (d, v)}


def _is_shape(x) -> bool:
    return isinstance(x, tuple) and all(isinstance(i, int) for i in x)


def init(m: dict, key, dtype=jnp.float32):
    """The weights from ``key``; traceable, so callers jit it."""
    paths, treedef = jax.tree_util.tree_flatten_with_path(shapes(m), is_leaf=_is_shape)
    keys = jax.random.split(key, len(paths))
    leaves = []
    for (path, shape), k in zip(paths, keys):
        name = jax.tree_util.keystr(path)
        z = jax.random.normal(k, shape, jnp.float32)
        if "norm" in name:
            out = jnp.ones(shape)
        elif "embed" in name or "head" in name:
            out = 0.02 * z
        else:
            out = z / math.sqrt(shape[-2])
        leaves.append(out.astype(dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


# ------------------------------------------------------------------- YaRN
def mscale(factor: float, scale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * scale * math.log(factor) + 1.0


def yarn_inv_freq(dim: int, base: float, rs: dict):
    """(dim/2,) inverse frequencies and the ramp's (low, high) dimensions."""
    def correction_dim(rotations):
        return (dim * math.log(rs["original_max_position_embeddings"]
                               / (rotations * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(correction_dim(rs["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rs["beta_slow"])), dim - 1)
    pos = np.arange(0, dim, 2, dtype=np.float32) / np.float32(dim)
    freq_extra = 1.0 / (np.float32(base) ** pos)
    freq_inter = 1.0 / (np.float32(rs["factor"]) * np.float32(base) ** pos)
    span = high - low if high != low else 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / np.float32(span), 0, 1)
    mask = 1.0 - ramp
    return (freq_inter * (1 - mask) + freq_extra * mask).astype(np.float32), low, high


def _rope_tables(m: dict, l: int):
    rs = m["rope_scaling"]
    inv, _, _ = yarn_inv_freq(m["qk_rope_head_dim"], m["rope_theta"], rs)
    ang = np.arange(l, dtype=np.float32)[:, None] * inv[None, :]
    s = mscale(rs["factor"], rs["mscale"]) / mscale(rs["factor"], rs["mscale_all_dim"])
    return jnp.asarray(np.cos(ang) * s), jnp.asarray(np.sin(ang) * s)


def _rotate(x, cos, sin):
    """x (b, l, heads, dim): turn the two halves by the position's angles."""
    half = x.shape[-1] // 2
    c, s = cos[:, None, :].astype(x.dtype), sin[:, None, :].astype(x.dtype)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


# ----------------------------------------------------------------- forward
def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * scale


def _swiglu(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def _mla(p, x, m, cos, sin):
    b, l, _ = x.shape
    h, nope, rope, vd, r = (m["num_attention_heads"], m["qk_nope_head_dim"],
                            m["qk_rope_head_dim"], m["v_head_dim"], m["kv_lora_rank"])
    q = (x @ p["wq"]).reshape(b, l, h, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rotate(q[..., nope:], cos, sin)], axis=-1)
    ckv = x @ p["w_dkv"]
    c = _rms(ckv[..., :r], p["kv_norm"], m["rms_norm_eps"])
    k_rope = _rotate(ckv[..., None, r:], cos, sin)                     # (b, l, 1, rope)
    k_nope = (c @ p["w_uk"]).reshape(b, l, h, nope)
    v = (c @ p["w_uv"]).reshape(b, l, h, vd)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, l, h, rope))], axis=-1)
    rs = m["rope_scaling"]
    scale = mscale(rs["factor"], rs["mscale_all_dim"]) ** 2 / math.sqrt(nope + rope)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    s = jnp.where(jnp.tril(jnp.ones((l, l), bool)), s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1).astype(x.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(b, l, h * vd) @ p["wo"]


def _moe(p, x, m):
    """The held experts' gated outputs and the shared experts', and the
    layer's sequence-wise balance loss over all the router's experts."""
    e, k = m["router_experts"], m["num_experts_per_tok"]
    probs = jax.nn.softmax(x.astype(jnp.float32) @ p["router"].astype(jnp.float32), axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                               # (b, l, k)
    out = _swiglu(x, p["shared"]["w_gate"], p["shared"]["w_up"], p["shared"]["w_down"])
    for j in range(m["n_routed_experts"]):
        g = jnp.sum(jnp.where(idx == m["expert_start"] + j, gates, 0.0), axis=-1)
        out = out + g[..., None].astype(x.dtype) * _swiglu(
            x, p["w_gate"][j], p["w_up"][j], p["w_down"][j])
    b, l, _ = x.shape
    counts = jnp.stack([jnp.sum(idx == i, axis=(1, 2)) for i in range(e)], axis=-1)
    ce = counts.astype(jnp.float32) / (l * k / e)                      # (b, E)
    aux = m["aux_loss_alpha"] * jnp.mean(jnp.sum(ce * jnp.mean(probs, axis=1), axis=-1))
    return out, aux


def _layer(p, x, m, cos, sin, moe: bool):
    eps = m["rms_norm_eps"]
    x = x + _mla(p["mixer"], _rms(x, p["norm1"], eps), m, cos, sin)
    h = _rms(x, p["norm2"], eps)
    if moe:
        y, aux = _moe(p["ffn"], h, m)
        return x + y, aux
    f = p["ffn"]
    return x + _swiglu(h, f["w_gate"], f["w_up"], f["w_down"]), jnp.zeros((), jnp.float32)


def forward(params, tokens, m: dict, remat: bool = False):
    """Logits over the vocabulary slice and the summed balance loss; the
    dense layers, then the MoE layers, each a loop over its stack."""
    cos, sin = _rope_tables(m, tokens.shape[1])
    x = params["embed"][tokens]
    aux = jnp.zeros((), jnp.float32)
    for stack, moe in ((params["dense"], False), (params["blocks"]["slot0"], True)):
        layer = functools.partial(_layer, m=m, cos=cos, sin=sin, moe=moe)
        if remat:
            layer = jax.checkpoint(layer)
        x, a = jax.lax.scan(lambda h, p: layer(p, h), x, stack)
        aux = aux + jnp.sum(a)
    x = _rms(x, params["final_norm"], m["rms_norm_eps"])
    return x @ params["head"], aux


def loss(params, batch, m: dict, remat: bool = False):
    logits, aux = forward(params, batch["tokens"], m, remat)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.mean(jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1))
    return nll + aux


# --------------------------------------------------------------------- run
def _leaf_norms(tree) -> np.ndarray:
    return np.array([float(jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32)))))
                     for a in jax.tree_util.tree_leaves(tree)])


def step_program(cfg: dict):
    """The jitted momentum-SGD step (v <- beta v + g, p <- p - lr v)."""
    beta = cfg["beta"]

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(p, v, batch, lr):
        lv, g = jax.value_and_grad(functools.partial(loss, m=cfg, remat=True))(p, batch)
        v = jax.tree_util.tree_map(lambda vv, gg: beta * vv + gg, v, g)
        p = jax.tree_util.tree_map(lambda pp, vv: pp - lr * vv, p, v)
        return p, v, lv

    return step


def run(cfg: dict, traffic: dict, init_fn, batches: list, *,
        dtype=jnp.float32, precision: str = "highest", batch_frac: float = 1.0) -> dict:
    """The pod's momentum-SGD steps over ``batches`` (v <- beta v + g,
    p <- p - lr v, lr = 1 / (R (step + 1)^0.499)); readings as
    ``refs/fedstep_ref.run``: each step's loss, the per-leaf norms of the
    first gradient and of the change after the last step. ``init_fn()``
    gives the float32 initial weights; it is called twice."""
    if traffic["pods"] != 1:
        raise NotImplementedError("the reference replays one pod, without gossip")
    step, lr_r = step_program(cfg), cfg["lr_r"]

    with jax.default_matmul_precision(precision):
        p = jax.jit(lambda t: jax.tree_util.tree_map(lambda a: a.astype(dtype), t))(init_fn())
        v = jax.tree_util.tree_map(jnp.zeros_like, p)
        losses, first = [], None
        for s, batch in enumerate(batches):
            lr = jnp.asarray(1.0 / (lr_r * float(s + 1) ** 0.499), dtype)
            rows = batch["tokens"].shape[1]
            keep = max(1, int(rows * batch_frac))
            if rows % keep:
                raise ValueError(f"batch_frac {batch_frac} leaves {keep} of {rows} rows")
            # the kept rows repeated to the batch's shape: the same means (of
            # the tokens' loss and of the sequences' balance loss), and the
            # program the whole batch compiled
            b = {k: jnp.asarray(a[0, np.arange(rows) % keep]) for k, a in batch.items()}
            p, v, lv = step(p, v, b, lr)
            losses.append([float(lv)])
            if s == 0:
                first = _leaf_norms(v)[None]
        del v
        p0 = init_fn()
        third = _leaf_norms(jax.tree_util.tree_map(lambda a, b: a.astype(jnp.float32) - b, p, p0))
    return {"losses": losses, "first": first, "third": third[None]}
