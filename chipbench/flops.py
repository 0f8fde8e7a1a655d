"""Model FLOPs and least bytes, counted from shapes.

Kept with the benchmark so that every change is divided by the same work. A
multiply-add counts 2 FLOPs; a backward pass counts twice its forward
(gradients of activations and of weights); lookups, norms and elementwise
work count 0.
"""
from __future__ import annotations

__all__ = ["decoder_train_flops_per_token", "round_flops", "qdq_round_bytes",
           "sample_forward_flops"]


def decoder_train_flops_per_token(m: dict, seq: int) -> float:
    """Forward + backward FLOPs per token of a Llama-style decoder: 6 per
    matmul parameter (the layers' projections and MLP, and the LM head; the
    embedding lookup counts 0), plus attention's score and value products
    over the whole ``seq`` x ``seq`` square, 12 * layers * seq * heads *
    head_dim (PaLM's count)."""
    d, ff, v = m["hidden_size"], m["intermediate_size"], m["vocab_size"]
    h, kv, n = m["num_attention_heads"], m["num_key_value_heads"], m["num_hidden_layers"]
    hd = d // h
    per_layer = d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * ff
    matmul_params = n * per_layer + d * v
    return 6.0 * matmul_params + 12.0 * n * seq * h * hd


def sample_forward_flops(m: dict) -> float:
    """Forward FLOPs of one sample of the protocol models."""
    if m["kind"] == "fnn":
        dims = m["dims"]
        return 2.0 * sum(a * b for a, b in zip(dims[:-1], dims[1:]))
    if m["kind"] == "lstm":
        h, total, d_in = m["hidden"], 0.0, m["embed"]
        for _ in range(m["layers"]):
            total += 2.0 * (d_in * 4 * h + h * 4 * h)
            d_in = h
        return total * m["seq_len"] + 2.0 * h * m["vocab"]
    raise ValueError(m["kind"])


def round_flops(m: dict, chains: int, walk: int, batch: int) -> float:
    """Model FLOPs of one protocol round: forward and backward of the
    chains' K x M batches (every chain computes its gradient at every step,
    also where a straggler mask then drops it), plus the loss evaluation of
    the M final chain models on their last batch."""
    fwd = sample_forward_flops(m)
    return 3.0 * fwd * walk * chains * batch + fwd * chains * batch


def qdq_round_bytes(d_pad: int, chains: int, walk: int, lanes: int = 128) -> float:
    """Least HBM bytes of one round's quantize-dequantize kernel calls.

    Hop hand-off, once per walk step: the (M, d_pad) f32 payload and its
    base are read and the result written, plus two f32 per 128-lane row of
    side information (interval and norm). Aggregation, once per round: the
    (K*M, d_pad) payload read and written, plus its side information."""
    rows = d_pad // lanes
    hop = chains * (3 * d_pad + 2 * rows) * 4
    agg = walk * chains * (2 * d_pad + 2 * rows) * 4
    return float(walk * hop + agg)
