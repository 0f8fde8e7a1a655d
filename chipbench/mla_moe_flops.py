"""Model FLOPs of the MLA + routed-expert decoder (DeepSeek-V2-Lite's
layers), counted from shapes, as ``flops.py`` counts the Llama-style
decoder: a multiply-add counts 2 FLOPs, a backward pass twice its forward,
lookups, norms, routing and elementwise work 0."""
from __future__ import annotations

__all__ = ["train_flops_per_token", "experts_least_flops"]


def _mla_params(m: dict) -> int:
    """Matmul parameters of one MLA layer in its published, unabsorbed
    form (kv_b_proj as kv_lora_rank x heads (qk_nope + v))."""
    d, h = m["hidden_size"], m["num_attention_heads"]
    nope, rope, vd, r = (m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
                         m["kv_lora_rank"])
    return d * h * (nope + rope) + d * (r + rope) + r * h * (nope + vd) + h * vd * d


def train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs per token: 6 per matmul parameter (every
    layer's MLA; the dense layers' SwiGLU; each MoE layer's router, shared
    experts and the held routed experts at their expected share k * held /
    E of the tokens; the head), plus attention's score and value products
    over the whole seq x seq square, 6 * seq * heads * (qk_nope + qk_rope +
    v) per layer. Weight absorption does not change the count."""
    d, v = m["hidden_size"], m["vocab_size"]
    n, nd = m["num_hidden_layers"], m["first_k_dense_replace"]
    fe, e, k = m["moe_intermediate_size"], m["router_experts"], m["num_experts_per_tok"]
    routed = k * m["n_routed_experts"] / e
    moe = d * e + (m["n_shared_experts"] + routed) * 3 * d * fe
    matmul = n * _mla_params(m) + nd * 3 * d * m["intermediate_size"] + (n - nd) * moe + d * v
    qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attention = 6.0 * n * seq * m["num_attention_heads"] * (qk + m["v_head_dim"])
    return 6.0 * matmul + attention


def experts_least_flops(m: dict, tokens: int) -> float:
    """The least FLOPs of the held experts' grouped matmuls in one training
    step: forward and backward of the three SwiGLU matmuls, 18 * rows * d *
    moe_intermediate_size per MoE layer, over the rows the held experts
    expect, tokens * k * held / E."""
    rows = tokens * m["num_experts_per_tok"] * m["n_routed_experts"] / m["router_experts"]
    layers = m["num_hidden_layers"] - m["first_k_dense_replace"]
    return 18.0 * rows * m["hidden_size"] * m["moe_intermediate_size"] * layers
