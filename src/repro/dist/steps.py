"""Sharded step builders for the pod-scale meshes.

Each builder returns a jit-able step function plus the PartitionSpecs of its
parameter tree (from repro.dist.sharding), so callers can ``jax.jit(fn,
in_shardings=named(specs, mesh))`` or ``jax.device_put`` real arrays:

* ``make_train_step`` — sharded fwd/bwd + decreasing-lr SGD with momentum
  (paper §VI-B schedule), optional remat.
* ``make_serve_step`` — one batched decode step over the KV-cache path
  (``slots=True`` for the continuous-batching per-slot variant).
* ``make_prefill_step`` — chunked batched prefill writing at per-slot
  offsets into the decode cache layout (the serve engine's admission path).
* ``make_gossip_step`` — per-pod stacked params mixed with the
  dist.gossip ring/expander weights (doubly stochastic, so the global mean
  over the pod axis is preserved — paper Eq. 11 at pod scale).
* ``opt_specs`` (re-exported from dist.sharding) — PartitionSpecs for
  optimizer-state mirrors: fp32 masters and 8-bit moments can shard
  differently from bf16 params (ZeRO-style data-sharding of leaves the
  param rules replicate).
* ``make_fed_train_step`` — the decomposed DFedRW deployment: per-pod local
  momentum-SGD steps (no cross-pod collectives) + a gossip mix every
  ``gossip.every`` steps, quantizing payloads when ``gossip.quant_bits < 32``
  (QDFedRW, Eq. 12/14).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.gossip import GossipConfig, gossip_mix
from repro.dist.sharding import opt_specs, param_specs
from repro.models import transformer as T
from repro.models.config import ArchConfig
from repro.obs import scopes
from repro.optim.sgd import decreasing_lr, momentum_sgd

__all__ = [
    "make_train_step",
    "make_serve_step",
    "make_prefill_step",
    "make_gossip_step",
    "make_fed_train_step",
    "opt_specs",
]


def make_train_step(cfg: ArchConfig, mesh, *, lr_r: float = 5.0,
                    beta: float = 0.9, remat: bool = True,
                    unroll: bool = False):
    """step_fn(params, vel, batch, step) -> (params, vel, loss).

    ``vel`` is a zeros_like mirror of ``params`` (momentum); place it with
    ``opt_specs(abstract_params, mesh)`` when its precision differs from the
    params' (fp32 masters / 8-bit moments next to bf16 weights — the state
    may shard where params replicate). The learning rate follows the
    paper's decreasing schedule 1/(lr_r * (step+1)^q)."""
    p_specs = param_specs(T.abstract_params(cfg), mesh)

    def step_fn(params, vel, batch, step):
        loss, grads = jax.value_and_grad(
            lambda p: T.loss_fn(cfg, p, batch, remat=remat, unroll=unroll)
        )(params)
        lr = decreasing_lr(step + 1, r=lr_r)
        params, vel = momentum_sgd(params, vel, grads, lr, beta)
        return params, vel, loss

    return step_fn, p_specs


def make_serve_step(cfg: ArchConfig, mesh, *, unroll: bool = False,
                    slots: bool = False):
    """serve_fn(params, cache, token) -> (logits, new_cache).

    slots=True builds the continuous-batching variant
    ``serve_fn(params, cache, token, positions, active)`` where every cache
    row is an independent request slot at its own absolute position and
    ``active`` freezes retired/free rows (see T.decode_step)."""
    p_specs = param_specs(T.abstract_params(cfg), mesh)

    if slots:
        def serve_fn(params, cache, token, positions, active):
            return T.decode_step(cfg, params, cache, token, unroll=unroll,
                                 positions=positions, active=active)
    else:
        def serve_fn(params, cache, token):
            return T.decode_step(cfg, params, cache, token, unroll=unroll)

    return serve_fn, p_specs


def make_prefill_step(cfg: ArchConfig, mesh, *, unroll: bool = False):
    """prefill_fn(params, cache, tokens (B,C), positions (B,), n_valid (B,))
    -> (logits (B,C,V), new_cache): chunked batched prefill into the decode
    cache layout at per-slot offsets (see T.prefill_chunk). Shares
    ``param_specs``/``cache_specs`` sharding with the decode step — the
    whole serve path lowers onto one mesh."""
    p_specs = param_specs(T.abstract_params(cfg), mesh)

    def prefill_fn(params, cache, tokens, positions, n_valid):
        return T.prefill_chunk(cfg, params, cache, tokens, positions, n_valid,
                               unroll=unroll)

    return prefill_fn, p_specs


def make_gossip_step(cfg: ArchConfig, mesh, gossip: GossipConfig, *,
                     dtype=jnp.bfloat16):
    """Cross-pod decentralized averaging over per-pod stacked params.

    Returns (gstep, p_specs, fed_abstract):
      gstep(params, key) -> mixed params, where ``params`` stacks one model
      per pod along a leading dim sharded over ``gossip.axis``. The mixing
      weights (dist.gossip.mixing_weights) are doubly stochastic, so the
      global mean over the axis is preserved. ``key`` seeds the stochastic
      quantizer when ``gossip.quant_bits < 32`` (ignored at fp32).
      fed_abstract is the ShapeDtypeStruct tree of the stacked params.
    """
    base = T.abstract_params(cfg, dtype)
    n_pods = dict(mesh.shape)[gossip.axis]
    p_specs = param_specs(base, mesh, fed_axis=gossip.axis)
    fed_abstract = jax.tree_util.tree_map(
        lambda l: jax.ShapeDtypeStruct((n_pods, *l.shape), l.dtype), base)

    def gstep(params, key):
        return gossip_mix(params, p_specs, mesh, gossip, key)

    return gstep, p_specs, fed_abstract


def make_fed_train_step(cfg: ArchConfig, mesh, gossip: GossipConfig, *,
                        lr_r: float = 5.0, beta: float = 0.9,
                        remat: bool = True, unroll: bool = False,
                        dtype=jnp.bfloat16, scheduled: bool = False):
    """The DFedRW pod deployment: step_fn(params, vel, batch, step, key)
    -> (params, vel, losses), with one loss per pod: the step has no
    cross-pod reduction, so its only collectives are the gossip's permutes
    (the caller averages the losses on the host).

    ``params``/``vel`` stack one model per pod (leading dim over
    ``gossip.axis``); ``batch`` leaves carry the matching leading group dim
    (see batch_specs(..., fed_axis=...)). Every step runs an independent
    local momentum-SGD step per pod (vmapped over the stack — XLA keeps it
    pod-local, no cross-pod collectives); every ``gossip.every``-th step the
    pods additionally gossip-average (quantized when quant_bits < 32).
    ``dtype`` sets the returned ``fed_abstract`` (match it to the params the
    step will actually run on, e.g. float32 for the CPU launcher).

    ``scheduled=True`` builds the trace-driven variant
    ``step_fn(params, vel, batch, step, do_gossip, key)``: the gossip
    trigger becomes a data operand instead of the static modulo, so a
    recorded simulator timeline drives the deployment directly — feed one
    element of ``SimTrace.gossip_flags()`` per step and the pods gossip
    exactly when the simulated fleet aggregated (same compiled program for
    every step; ``gossip.every`` is ignored)."""
    gstep, p_specs, fed_abstract = make_gossip_step(cfg, mesh, gossip, dtype=dtype)
    every = max(int(gossip.every), 1)

    def forward(p, b):
        with jax.named_scope(scopes.FORWARD):
            return T.loss_fn(cfg, p, b, remat=remat, unroll=unroll)

    def gossip_step(p, key):
        with jax.named_scope(scopes.GOSSIP):
            return gstep(p, key)

    def _local_step(params, vel, batch, step):
        losses, grads = jax.vmap(jax.value_and_grad(forward))(params, batch)
        with jax.named_scope(scopes.OPTIMIZER):
            lr = decreasing_lr(step + 1, r=lr_r)
            params, vel = momentum_sgd(params, vel, grads, lr, beta)
        return params, vel, losses

    if scheduled:
        def step_fn(params, vel, batch, step, do_gossip, key):
            params, vel, loss = _local_step(params, vel, batch, step)
            params = jax.lax.cond(
                do_gossip, lambda p: gossip_step(p, key), lambda p: p, params)
            return params, vel, loss

        return step_fn, p_specs, fed_abstract

    def step_fn(params, vel, batch, step, key):
        params, vel, loss = _local_step(params, vel, batch, step)
        if every == 1:
            params = gossip_step(params, key)
        else:
            params = jax.lax.cond(
                (step + 1) % every == 0,
                lambda p: gossip_step(p, key), lambda p: p, params)
        return params, vel, loss

    return step_fn, p_specs, fed_abstract
