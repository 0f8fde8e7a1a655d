"""Driver of the MLA + routed-expert pod-step cells (DeepSeek-V2-Lite's
layers): the jitted step of ``dist.steps.make_fed_train_step`` over the
model's ``ArchConfig`` (leading dense layers, MLA with YaRN rope, the
dropless expert layer told which experts it holds), with each layer
recomputed in the backward pass (``remat``), called as the pod launcher's
loop calls it, as ``drivers/fedstep.py`` does for the Llama-style decoder.

Set-up makes the weights (``refs/mla_moe.init``) and zero momentum on the
device from the seed in one jitted call and runs the first three steps:
they compile the step and are the steps the reference
(``refs/mla_moe.run``) replays. The window continues from the same step
and state.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from chipbench import mla_moe_flops, traffic
from chipbench.drivers import fedstep
from chipbench.refs import mla_moe

CHECKED = fedstep.CHECKED


def arch_config(cfg: dict):
    """The program's ``ArchConfig`` of the configuration file ``cfg``."""
    from repro.models.config import ArchConfig, MLAConfig, MoEConfig, YarnConfig

    rs = cfg["rope_scaling"]
    return ArchConfig(
        name=cfg["name"], n_layers=cfg["num_hidden_layers"],
        n_dense_layers=cfg["first_k_dense_replace"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"], n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"], attn_type="mla",
        mla=MLAConfig(kv_lora_rank=cfg["kv_lora_rank"], qk_nope_dim=cfg["qk_nope_head_dim"],
                      qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"]),
        ffn_pattern=("moe",),
        moe=MoEConfig(n_experts=cfg["router_experts"], top_k=cfg["num_experts_per_tok"],
                      n_shared=cfg["n_shared_experts"], d_expert=cfg["moe_intermediate_size"],
                      router_aux_weight=cfg["aux_loss_alpha"], expert_start=cfg["expert_start"],
                      n_held=cfg["n_routed_experts"], norm_topk=cfg["norm_topk_prob"],
                      aux="seq" if cfg["seq_aux"] else "switch",
                      expert_dtype=cfg["expert_dtype"]),
        rope_theta=cfg["rope_theta"],
        yarn=YarnConfig(factor=rs["factor"],
                        original_max_positions=rs["original_max_position_embeddings"],
                        beta_fast=rs["beta_fast"], beta_slow=rs["beta_slow"],
                        mscale=rs["mscale"], mscale_all_dim=rs["mscale_all_dim"]),
        norm_eps=cfg["rms_norm_eps"], tie_embeddings=cfg["tie_word_embeddings"])


class Session(fedstep.Session):
    def __init__(self, cfg: dict, wl: dict, seed: int, chips: int):
        from repro.dist.gossip import GossipConfig
        from repro.dist.sharding import batch_specs, named
        from repro.dist.steps import make_fed_train_step
        from repro.launch.mesh import make_mesh

        t = self.t = wl["traffic"]
        self.cfg, g = cfg, t["pods"]
        self.mesh = make_mesh((g, chips // g, 1), ("pod", "data", "model"),
                              jax.devices()[:chips])
        gossip = GossipConfig(axis="pod", topology=t["topology"], every=t["every"],
                              quant_bits=t["bits"])
        step_fn, p_specs, fed_abstract = make_fed_train_step(
            arch_config(cfg), self.mesh, gossip, lr_r=cfg["lr_r"], beta=cfg["beta"],
            remat=cfg["remat"], dtype=jnp.float32)
        self.step = jax.jit(step_fn, donate_argnums=(0, 1))

        def stacked(key):
            return jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a, (g, *a.shape)),
                                          mla_moe.init(cfg, key))

        rng = np.random.default_rng(seed)
        self.wkey = traffic.key_words(rng)
        mine = jax.eval_shape(stacked, self.wkey)
        if (jax.tree_util.tree_structure(mine) != jax.tree_util.tree_structure(fed_abstract)
                or [a.shape for a in jax.tree_util.tree_leaves(mine)]
                != [a.shape for a in jax.tree_util.tree_leaves(fed_abstract)]):
            raise RuntimeError("the program's parameter tree differs from the benchmark's")
        shard = named(p_specs, self.mesh)
        self.params, self.vel = jax.jit(
            lambda k: (stacked(k), jax.tree_util.tree_map(jnp.zeros_like, stacked(k))),
            out_shardings=(shard, shard))(self.wkey)
        self.rng = rng
        self.key = jnp.asarray(traffic.key_words(rng))
        self.b_shard = named(batch_specs(self._batch_abstract(), self.mesh, fed_axis="pod"),
                             self.mesh)
        self.tokens = g * t["batch"] * t["seq"]
        self.i, self.failed = 0, 0

        def leaf_norms(tree):
            return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a), axis=tuple(range(1, a.ndim))))
                              for a in jax.tree_util.tree_leaves(tree)], axis=1)

        change = jax.jit(lambda p, k: leaf_norms(jax.tree_util.tree_map(
            lambda a, b: a - b, p, stacked(k))))
        self.batches, losses = [], []
        for s in range(CHECKED):
            losses.append(self._step())
            if s == 0:
                first = np.asarray(jax.jit(leaf_norms)(self.vel))
        self.readings = {"losses": losses, "first": first,
                         "third": np.asarray(change(self.params, self.wkey)),
                         "first_name": "grad1_gap"}
        self.counts = {
            "model_flops_per_call": self.tokens * mla_moe_flops.train_flops_per_token(
                cfg, t["seq"]),
            "experts_flops_per_call": mla_moe_flops.experts_least_flops(cfg, self.tokens)}

    def reference(self, **kw) -> dict:
        """The plain reference's readings of the checked calls; ``kw``
        (``dtype``, ``precision``, ``batch_frac``) goes to the reference."""
        init = jax.jit(lambda k: mla_moe.init(self.cfg, k))
        return mla_moe.run(self.cfg, self.t, lambda: init(self.wkey), self.batches, **kw)
