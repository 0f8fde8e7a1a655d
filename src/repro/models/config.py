"""Architecture configuration for the pod-scale model zoo.

One `ArchConfig` describes every assigned architecture (dense / MoE / MLA /
SSM / hybrid / enc-dec / VLM / audio) as a pattern of scanned layer blocks,
so a single forward implementation covers all ten.
"""
from __future__ import annotations

import dataclasses
from typing import Sequence

__all__ = ["MoEConfig", "MLAConfig", "SSMConfig", "YarnConfig", "ArchConfig"]


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    """A dropless routed-expert layer. The router scores all ``n_experts``;
    the layer holds (and computes) experts ``expert_start`` ..
    ``expert_start + n_held - 1``, one chip's share of an expert-parallel
    deployment (``n_held`` 0: all of them)."""

    n_experts: int = 8
    top_k: int = 2
    n_shared: int = 0          # shared (always-on) experts, deepseek-style
    d_expert: int = 0          # expert FFN hidden dim (0 => use d_ff)
    router_aux_weight: float = 0.01
    expert_start: int = 0      # first expert held here
    n_held: int = 0            # experts held here (0 => all n_experts)
    norm_topk: bool = True     # renormalise the top-k gates to sum to 1
    aux: str = "switch"        # balance loss: "switch" (top-1 fractions over
                               # the batch) | "seq" (DeepSeek's sequence-wise)
    expert_dtype: str | None = None  # input dtype of the held experts' grouped
                               # matmul (f32 accumulation); None: the activations'

    @property
    def held(self) -> int:
        return self.n_held or self.n_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    kv_lora_rank: int = 512
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    v_head_dim: int = 128


@dataclasses.dataclass(frozen=True)
class YarnConfig:
    """YaRN rope scaling (arXiv:2309.00071), as DeepSeek-V2 configures it."""

    factor: float = 40.0
    original_max_positions: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int = 128       # N
    head_dim: int = 64         # P
    n_groups: int = 1          # B/C groups (GVA-style)
    chunk: int = 256           # SSD chunk length
    d_conv: int = 4
    expand: int = 2            # d_inner = expand * d_model


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    # Layer pattern within one scanned block; the model is `block_pattern`
    # repeated n_layers/len(block_pattern) times. Entries: "attn" | "mamba".
    block_pattern: Sequence[str] = ("attn",)
    # Which pattern slots are MoE ("moe") vs dense ("dense"); same length as
    # block_pattern, or a single-element tuple broadcast to all slots.
    ffn_pattern: Sequence[str] = ("dense",)
    n_dense_layers: int = 0             # leading layers of attention + a
                                        # SwiGLU of width d_ff, before the
                                        # scanned blocks (DeepSeek's
                                        # first_k_dense_replace)
    attn_type: str = "gqa"             # "gqa" | "mla"
    qkv_bias: bool = False
    head_dim: int = 0                   # 0 => d_model // n_heads
    moe: MoEConfig | None = None
    mla: MLAConfig | None = None
    ssm: SSMConfig | None = None
    enc_dec: bool = False               # seamless: encoder-decoder
    n_enc_layers: int = 0               # encoder layers when enc_dec
    frontend: str = "none"              # "none" | "vision" | "audio" (stubs)
    frontend_tokens: int = 256          # patches/frames prepended (stub)
    rope_theta: float = 1e6
    yarn: YarnConfig | None = None      # YaRN-scaled rope frequencies
    norm_eps: float = 1e-5
    sliding_window: int = 0             # 0 = full attention
    tie_embeddings: bool = False
    use_pallas_ssd: bool = False        # route SSD through the Pallas kernel
                                        # (interpret-mode on CPU; fused on TPU)
    attn_logits_bf16: bool = False      # beyond-paper perf option: keep the
                                        # (L x L) attention logits in bf16
                                        # (max-subtraction still exact),
                                        # halving the dominant score bytes
    attn_bp_axes: tuple = ("data", "model")  # axes for batch-parallel attention
    attn_batch_parallel: bool = False   # beyond-paper perf option: when
                                        # n_heads % model-axis != 0, compute
                                        # attention batch-parallel over
                                        # (data, model) and keep only FFN
                                        # tensor-parallel (see dist/sharding)
    citation: str = ""

    @property
    def head_dim_(self) -> int:
        return self.head_dim or (self.d_model // self.n_heads)

    @property
    def n_blocks(self) -> int:
        n = self.n_layers - self.n_dense_layers
        assert n % len(self.block_pattern) == 0, (
            f"{self.name}: {n} scanned layers not divisible by "
            f"pattern {len(self.block_pattern)}"
        )
        return n // len(self.block_pattern)

    @property
    def rope_dim(self) -> int:
        return self.mla.qk_rope_dim if self.attn_type == "mla" else self.head_dim_

    def ffn_kind(self, slot: int) -> str:
        if len(self.ffn_pattern) == 1:
            return self.ffn_pattern[0]
        return self.ffn_pattern[slot]

    @property
    def is_ssm_only(self) -> bool:
        return all(k == "mamba" for k in self.block_pattern)

    @property
    def has_attention(self) -> bool:
        return any(k == "attn" for k in self.block_pattern)

    @property
    def sub_quadratic(self) -> bool:
        """True when long-context decode is natively cheap: SSM/hybrid (the
        cache does not grow with context for mamba layers) or an explicit
        sliding window."""
        return self.is_ssm_only or ("mamba" in self.block_pattern) or self.sliding_window > 0

    def with_sliding_window(self, window: int) -> "ArchConfig":
        return dataclasses.replace(self, sliding_window=window)

    def param_count(self) -> int:
        """Analytic parameter count (for 6ND model-FLOPs and memory napkin)."""
        d, ff, v = self.d_model, self.d_ff, self.vocab
        hd = self.head_dim_
        total = v * d  # embed
        if not self.tie_embeddings:
            total += d * v  # lm head
        if self.attn_type == "mla":
            m = self.mla
            attn = d * self.n_heads * (m.qk_nope_dim + m.qk_rope_dim)
            attn += d * (m.kv_lora_rank + m.qk_rope_dim)
            attn += m.kv_lora_rank * self.n_heads * (m.qk_nope_dim + m.v_head_dim)
            attn += self.n_heads * m.v_head_dim * d
        else:
            attn = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
        total += self.n_dense_layers * (attn + 3 * d * ff + 2 * d)
        kinds = list(self.block_pattern)
        for slot, kind in enumerate(kinds):
            per = 0
            if kind == "attn":
                per += attn
            else:  # mamba
                s = self.ssm
                d_in = s.expand * d
                n_h = d_in // s.head_dim
                per += d * (2 * d_in + 2 * s.n_groups * s.state_dim + n_h)  # in_proj
                per += d_in * d  # out_proj
                per += s.d_conv * (d_in + 2 * s.n_groups * s.state_dim)
                per += 3 * n_h  # A_log, D, dt_bias
            fk = self.ffn_kind(slot)
            if fk == "none":
                per += d  # only norm1
                total += per * self.n_blocks
                continue
            if fk == "moe":
                mo = self.moe
                de = mo.d_expert or ff
                per += d * mo.n_experts  # router
                per += (mo.held + mo.n_shared) * 3 * d * de
            elif fk == "dense":
                per += 3 * d * ff  # swiglu
            per += 2 * d  # norms
            total += per * self.n_blocks
        if self.enc_dec:
            # encoder layers: attn + dense ffn (+ cross-attn in decoder counted above? keep simple)
            per = d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd + self.n_heads * hd * d
            per += 3 * d * ff + 2 * d
            total += per * self.n_enc_layers
            # decoder cross-attention
            total += (d * self.n_heads * hd + 2 * d * self.n_kv_heads * hd
                      + self.n_heads * hd * d + d) * self.n_layers
        return int(total)

    def active_param_count(self) -> int:
        """Active params per token (MoE: only routed top-k + shared)."""
        if self.moe is None:
            return self.param_count()
        full = self.param_count()
        mo = self.moe
        de = mo.d_expert or self.d_ff
        n_moe_slots = sum(1 for s in range(len(self.block_pattern)) if self.ffn_kind(s) == "moe")
        inactive = max(mo.held - mo.top_k, 0) * 3 * self.d_model * de
        return int(full - inactive * n_moe_slots * self.n_blocks)
