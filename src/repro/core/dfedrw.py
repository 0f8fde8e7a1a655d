"""DFedRW and QDFedRW protocol engines (paper Alg. 1 / Alg. 2).

Flat-buffer architecture
------------------------
The n federated client models live as ONE ``(n, d_pad)`` float32 matrix
(``repro.core.flatten``): every leaf of the model pytree owns a 128-aligned
column segment, so each protocol operation of a communication round is a
single 2-D array op on that matrix:

  1. *Walk planning* (host, numpy, vectorized): M Metropolis-Hastings chains
     with straggler-dependent lengths K_m (repro.core.walk), one
     ``rng.integers`` draw for the whole (M, K, B) batch-index tensor.
  2. *Chain SGD* (Eq. 10): the M chain models are M rows; each scan step is
     one vmapped gradient on the flat vectors, masked by chain activity,
     with the paper's globally decreasing step size eta^kbar.
  3. *w^{t,last} write*: one winner election over all active chains' rows;
     ties (two chains visiting the same device in one step) break by chain
     order exactly like the sequential reference (`flatten.elect_writers`).
  4. *Aggregation* (Eq. 11 / Eq. 14): one gather of the (A, n_agg) neighbor
     rows, one weighted sum.

The changed rows reach the matrix through the row-merge kernel
(`repro.kernels.rowmerge.merge_rows`), one in-place pass over the matrix's
8-row tile groups: with the Eq. 14 updates in the same pass at
bits < 32, in a pass before and one after the Eq. 11 mix at 32.

QDFedRW (Alg. 2) sends stochastically quantized parameter *differences* on
every cross-device hop (Eq. 13) and in aggregation (Eq. 14). The flat engine
runs the quantizer as ONE fused Pallas kernel call per payload
(`repro.kernels.quantize.payload_quantize_dequantize`): per-leaf segments of
the flat buffer carry their own adaptive grid (segment-wise norms), so the
wire format is that of the per-leaf reference in ``repro.core.quantization``
(parity tests in tests/test_flat_engine.py); the kernel's bit-exact oracle
is ``repro.kernels.quantize.ref``.

``DFedRWConfig.engine`` selects the implementation: ``"flat"`` (default,
vectorized + Pallas) or ``"reference"`` (the seed per-leaf/per-chain
engine, kept as the numerical oracle and benchmark baseline). Both share the
host-side planner, so seeded runs are comparable round by round. The flat
round function donates the device matrix (the state passed to a round is
consumed by it) and guards against shape-induced retraces (aggregation
plans are padded to fixed shapes).

The per-round inner loop is jitted once per (M, K, batch) shape; walk plans
and data gathers are cheap host-side numpy. The flat round program is built
from two halves, the walk (step 2) and the finalize (steps 3-4)
(`DFedRW.round_parts`), which the metal replay (``repro.sim.metal``) also
runs, sharding the walk over devices.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.flatten import (
    FlatSpec,
    elect_writers,
    flatten_tree,
    make_flat_spec,
    unflatten_tree,
)
from repro.core.graph import Topology
from repro.core.quantization import (
    QuantConfig,
    dequantize,
    quantize,
    validate_wire_bits,
    wire_bits,
)
from repro.core.walk import StragglerModel, WalkPlan, sample_walks
from repro.data.synthetic import FederatedDataset
from repro.kernels.quantize import payload_quantize_dequantize
from repro.kernels.rowmerge import ROW_GROUP, merge_rows
from repro.models.fnn import SmallModel
from repro.obs import scopes
from repro.optim.sgd import decreasing_lr

__all__ = ["DFedRWConfig", "DFedRWState", "DFedRW", "RoundMetrics"]


@dataclasses.dataclass(frozen=True)
class DFedRWConfig:
    m_chains: int = 5
    k_walk: int = 5
    agg_fraction: float = 0.25      # fraction of devices aggregating per round
    n_agg: int = 5                  # |N_A(i)| cap
    batch_size: int = 50
    lr_r: float = 5.0
    lr_q: float = 0.499
    quant: QuantConfig = dataclasses.field(default_factory=lambda: QuantConfig(bits=32))
    straggler: StragglerModel = dataclasses.field(default_factory=StragglerModel)
    chain_mode: bool = False        # large-scale LM mode (§VI-F): aggregate the
                                    # M chain-end models; chains persist across rounds
    engine: str = "flat"            # "flat" (vectorized + Pallas) | "reference"
    seed: int = 0


@dataclasses.dataclass
class DFedRWState:
    device_params: Any              # flat engine: (n, d_pad) matrix;
                                    # reference engine: pytree, leaves (n, ...)
    round: int = 0
    global_step: int = 0            # kbar counter
    chain_starts: np.ndarray | None = None  # chain mode: i_m^{t,0}
    comm_bits_total: float = 0.0
    comm_bits_busiest: float = 0.0
    updated: np.ndarray | None = None  # (n,) bool: device has trained/aggregated
                                       # at least once (evaluation averages over
                                       # these; un-touched devices still hold
                                       # their init and are not "the model")


@dataclasses.dataclass
class RoundMetrics:
    round: int
    train_loss: float
    comm_bits_round: float
    comm_bits_busiest_round: float
    gamma_hat: float


def _no_span(name: str) -> contextlib.nullcontext:
    """Stands in for ``Recorder.span`` when no recorder is attached."""
    return contextlib.nullcontext()


def _stack_params(params: Any, n: int) -> Any:
    return jax.tree_util.tree_map(lambda p: jnp.broadcast_to(p, (n, *p.shape)).copy(), params)


def gamma_hat_from_traj(grad_sq_traj: jax.Array, walk_mask: jax.Array) -> jax.Array:
    """Lemma-1 estimate ||g_last|| / ||g_first|| averaged over chains.

    Mask-general: the first/last *active* step of each chain brackets the
    ratio, so the non-prefix window masks of the asynchronous simulator (a
    resumed chain's leading column is a masked anchor re-gather, repro.sim)
    measure the executed slice only. For the synchronous planner's prefix
    masks this reduces exactly to steps 0 and K_m-1.

    Chains whose walk mask is entirely False performed no step this round;
    their g_last/g0 ratio is computed from pre-masking gradients and is pure
    noise, so they are excluded from the mean (a fully-masked chain can arise
    under custom straggler models even though `chain_lengths` floors K_m at 1).
    """
    m, k = walk_mask.shape
    active_steps = jnp.sum(walk_mask, axis=1)                      # (M,)
    k_first = jnp.argmax(walk_mask, axis=1)                        # 0 if none
    k_last = k - 1 - jnp.argmax(walk_mask[:, ::-1], axis=1)
    g0 = jnp.sqrt(grad_sq_traj[k_first, jnp.arange(m)] + 1e-12)
    g_last = jnp.sqrt(grad_sq_traj[k_last, jnp.arange(m)] + 1e-12)
    alive = active_steps > 0
    ratios = jnp.where(alive, g_last / g0, 0.0)
    return jnp.sum(ratios) / jnp.maximum(jnp.sum(alive), 1)


class DFedRW:
    """Runner binding (model, dataset, topology, config)."""

    def __init__(
        self,
        model: SmallModel,
        data: FederatedDataset,
        topo: Topology,
        cfg: DFedRWConfig,
    ):
        assert data.n_clients == topo.n, "dataset clients must match graph size"
        assert cfg.engine in ("flat", "reference"), cfg.engine
        self.model = model
        self.data = data
        self.topo = topo
        self.cfg = cfg
        self.rng = np.random.default_rng(cfg.seed)
        self._x = jnp.asarray(data.x)
        self._y = jnp.asarray(data.y)
        self.flat_spec: FlatSpec = make_flat_spec(
            jax.eval_shape(model.init, jax.random.PRNGKey(0))
        )
        self._trace_count = 0
        self._retraces_warned = 0   # retraces already reported via warnings
        self._retraces_obs = 0      # retraces already exported to the recorder
        self.obs = None             # optional repro.obs.Recorder (attach_obs)
        # Program table: one jitted round function per wire bit-width. The
        # fused qdq kernels take ``bits`` as a STATIC argument, so multi-bit
        # dispatch without retrace means pre-building one program per
        # supported width (prepare_bits) and selecting by per-round data
        # (execute_round(bits=...)). Each program traces exactly once at
        # fixed plan shapes; _programs_run tracks how many distinct programs
        # have executed so the retrace warning stays meaningful.
        self._round_fns: dict[int, Any] = {}
        self._round_parts: dict[int, tuple] = {}
        self._programs_run: set[int] = set()
        self.round_program(cfg.quant.bits)

    # ------------------------------------------------------------------ init
    def init_state(self, key: jax.Array) -> DFedRWState:
        params = self.model.init(key)
        starts = None
        if self.cfg.chain_mode:
            starts = self.rng.integers(0, self.topo.n, size=self.cfg.m_chains)
        if self.cfg.engine == "flat":
            vec = flatten_tree(params, self.flat_spec)
            device_params = jnp.repeat(vec[None, :], self.topo.n, axis=0)
        else:
            device_params = _stack_params(params, self.topo.n)
        return DFedRWState(
            device_params=device_params,
            chain_starts=starts,
            updated=np.zeros(self.topo.n, dtype=bool),
        )

    @property
    def trace_count(self) -> int:
        """How many times any round program has been (re)traced. With the
        per-bit-width program table this equals the number of DISTINCT widths
        executed so far (each program traces once at fixed plan shapes); it
        must stay constant across subsequent bit-width switches."""
        return self._trace_count

    @property
    def programs_run(self) -> tuple[int, ...]:
        """Distinct wire bit-widths whose compiled program has executed."""
        return tuple(sorted(self._programs_run))

    @property
    def retrace_count(self) -> int:
        """Traces beyond one per distinct executed width — every unit here is
        a compiled executable thrown away by an unstable plan shape."""
        if not self._programs_run:
            return 0
        return max(0, self._trace_count - len(self._programs_run))

    def attach_obs(self, rec) -> None:
        """Attach a ``repro.obs.Recorder``. Instrumentation is host-side
        Python at round boundaries only — never a callback inside the jitted
        round programs — so attaching a recorder changes no compiled program,
        no RNG stream and no output bit."""
        self.obs = rec

    def round_program(self, bits: int):
        """The jitted round program for a wire bit-width (built on first
        request; use prepare_bits to pre-build a controller's whole table).
        ``round_program(b).lower(*round_inputs(...))`` gives its compiled
        form without running it."""
        bits = validate_wire_bits(int(bits))
        fn = self._round_fns.get(bits)
        if fn is None:
            if self.cfg.engine == "flat":
                fn = self._build_round_fn_flat(bits)
            else:
                fn = self._build_round_fn_reference(bits)
            self._round_fns[bits] = fn
        return fn

    def prepare_bits(self, widths) -> None:
        """Pre-build the jitted program for every width an adaptive
        bits-policy may request, so a mid-run switch never constructs a new
        program object (tracing still happens on each program's first call —
        once per width, never again)."""
        for b in widths:
            self.round_program(b)

    @property
    def prepared_bits(self) -> tuple[int, ...]:
        return tuple(sorted(self._round_fns))

    def params_pytree(self, state: DFedRWState) -> Any:
        """The stacked per-device model pytree, independent of engine."""
        if self.cfg.engine == "flat":
            return unflatten_tree(state.device_params, self.flat_spec)
        return state.device_params

    # ---------------------------------------------------------- flat engine
    def round_parts(self, bits: int):
        """The flat round program's two halves for a wire width, built once
        and not jitted: ``walk``, the chain-SGD scan with its Eq. 13 hop qdq
        over the gathered (K, M, B, ...) ``(xb, yb)`` batches, returning the
        scan's ``(chain_final, key), (traj, grad_sq)``; and ``finalize``, the
        winner election, `merge_rows` writes, Eq. 11/14 aggregation and
        loss, returning ``(new_device_flat, loss, gamma_hat)``.
        ``round_program(bits)`` is the jit of finalize after walk; the metal
        replay (`repro.sim.metal`) shards the walk and jits the finalize."""
        bits = validate_wire_bits(int(bits))
        if bits not in self._round_parts:
            self._round_parts[bits] = self._build_round_parts(bits)
        return self._round_parts[bits]

    def _build_round_parts(self, bits: int):
        cfg = self.cfg
        quant_on = bits < 32
        model = self.model
        spec = self.flat_spec
        d_pad = spec.d_pad

        def loss_flat(vec, batch):
            return model.loss_fn(unflatten_tree(vec, spec), batch)

        grad_fn = jax.vmap(jax.grad(loss_flat))

        def walk(chain_flat, batches, walk_mask, kbar0, qkey):
            def scan_body(carry, inputs):
                chain_flat, qkey = carry
                xb, yb, step_k = inputs
                lr = decreasing_lr(kbar0 + step_k + 1, cfg.lr_r, cfg.lr_q)
                grads = grad_fn(chain_flat, (xb, yb))          # (M, d_pad)
                mask_k = walk_mask[:, step_k]
                stepped = jnp.where(
                    mask_k[:, None], chain_flat - lr * grads, chain_flat
                )
                # QDFedRW: the hand-off to the next device transmits
                # Q(w^{k+1} - w^k) with one wire tensor per leaf (Eq. 13);
                # the receiver reconstructs w^k + deq(Q(diff)) in the same
                # fused kernel pass.
                if quant_on:
                    with jax.named_scope(scopes.WALK_HOP_QDQ):
                        qkey, sub = jax.random.split(qkey)
                        stepped = payload_quantize_dequantize(
                            stepped - chain_flat,
                            spec,
                            per_message=False,
                            bits=bits,
                            s=cfg.quant.s,
                            key=sub,
                            base=chain_flat,
                        )
                return (stepped, qkey), (stepped, jnp.sum(grads * grads, axis=1))

            # Full unroll: K is small (a handful of walk steps) and the
            # rolled-loop form costs 5-8x per step on CPU — XLA can neither
            # fuse across the while-loop boundary nor keep the Pallas call's
            # buffers in place.
            steps = jnp.arange(walk_mask.shape[1], dtype=jnp.int32)
            return jax.lax.scan(
                scan_body, (chain_flat, qkey), (*batches, steps), unroll=True)

        def finalize(
            device_flat,              # (n, d_pad) f32 pre-round matrix
            traj,                     # (K, M, d_pad) f32 walk trajectory
            grad_sq_traj,             # (K, M) f32
            walk_devices,             # (M, K) int32
            walk_mask,                # (M, K) bool
            agg_rows,                 # (A, n_agg) int32 neighbor ids per aggregator
            agg_weights,              # (A, n_agg) f32 (n_l/m, zero-padded)
            agg_devices,              # (A,) int32 aggregating device ids (n = pad)
            agg_key,                  # PRNG key of the Eq. 14 qdq
            chain_final,              # (M, d_pad) f32 final chain models
            last_batch,               # their last (xb, yb)
        ):
            k, m = traj.shape[:2]
            n_dev = device_flat.shape[0]

            # w^{t,last} write, ONCE per round over the whole trajectory:
            # nothing reads the device matrix during the walk, so the
            # sequential per-step writes collapse into one winner election
            # (priorities replay the (step, chain) write order). The rows
            # themselves are written by the row merge (`merge_rows`), the
            # round's last writer of the donated matrix: at bits < 32 in one
            # pass with the aggregators' Eq. 14 rows, at 32 in a pass of its
            # own before the Eq. 11 mix reads the merged rows.
            with jax.named_scope(scopes.SCATTER):
                devs_flat = walk_devices.T.reshape(-1)         # step-major
                mask_flat = walk_mask.T.reshape(-1)
                _, wins = elect_writers(devs_flat, mask_flat, n_dev)
                # losers target an out-of-range row: they write nothing
                targets = jnp.where(wins, devs_flat, n_dev).reshape(k, m)
                if not quant_on:
                    dev_last = merge_rows(device_flat, targets, traj)

            with jax.named_scope(scopes.LOSS):
                gamma_hat = gamma_hat_from_traj(grad_sq_traj, walk_mask)

            # Decentralized aggregation (Eq. 11 / Eq. 14); padded aggregator
            # slots carry device ids >= n and zero weights -> dropped.
            if quant_on:
                # Eq. 14 payload: one broadcast message Q(w_l^{t,last} - w_l)
                # per walk-updated device (non-updated neighbors have zero
                # diffs, which quantize to zero — so only winner rows carry
                # signal, and the payload is the trajectory itself). The
                # aggregator weight matrix lands each message on every
                # aggregator listing the sender.
                with jax.named_scope(scopes.AGGREGATE_QDQ):
                    base_rows = device_flat[devs_flat]         # (K*M, d_pad)
                    diffs = jnp.where(wins[:, None],
                                      traj.reshape(k * m, d_pad) - base_rows, 0.0)
                    deq = payload_quantize_dequantize(
                        diffs,
                        spec,
                        per_message=True,
                        bits=bits,
                        s=cfg.quant.s,
                        key=agg_key,
                    )
                with jax.named_scope(scopes.AGGREGATE_MIX):
                    hits = agg_rows[:, :, None] == devs_flat[None, None, :]
                    w3 = (jnp.sum(agg_weights[:, :, None] * hits, axis=1)
                          * wins[None, :].astype(jnp.float32))  # (A, K*M)
                    upd = w3 @ deq                             # (A, d_pad)
                # an aggregator's row is its pre-round row plus its update,
                # also where it won the walk's write
                with jax.named_scope(scopes.SCATTER):
                    new_device_flat = merge_rows(device_flat, targets, traj,
                                                 agg_devices, upd)
            else:
                with jax.named_scope(scopes.AGGREGATE_MIX):
                    gathered = dev_last[agg_rows]              # (A, n_agg, d_pad)
                    avg = jnp.sum(agg_weights[..., None] * gathered, axis=1)
                    new_device_flat = merge_rows(dev_last, agg_devices, avg)

            # Mean train loss over the round's final chain models, on their
            # last batch (cheap monitoring signal).
            with jax.named_scope(scopes.LOSS):
                losses = jax.vmap(loss_flat)(chain_final, last_batch)
                loss = jnp.mean(losses)
            return new_device_flat, loss, gamma_hat

        return walk, finalize

    def _build_round_fn_flat(self, bits: int):
        walk, finalize = self.round_parts(bits)

        @functools.partial(jax.jit, donate_argnums=(0,))
        def round_fn(
            device_flat,              # (n, d_pad) f32 — donated
            walk_devices,             # (M, K) int32
            walk_mask,                # (M, K) bool
            batch_idx,                # (M, K, B) int64 into global data
            agg_rows,                 # (A, n_agg) int32 neighbor ids per aggregator
            agg_weights,              # (A, n_agg) f32 (n_l/m, zero-padded)
            agg_devices,              # (A,) int32 aggregating device ids (n = pad)
            kbar0,                    # scalar int32: global step before round
            qkey,                     # PRNG key for quantization
        ):
            self._trace_count += 1    # python side effect: fires on (re)trace only
            x, y = self._x, self._y
            # The walk's scope holds the gathers and the scan, so the body's
            # ops and the scan's own slicing and output stacking fall in it;
            # the hop's qdq scope nests inside.
            with jax.named_scope(scopes.WALK_SGD):
                chain_flat = device_flat[walk_devices[:, 0]]   # (M, d_pad)
                bidx_t = jnp.swapaxes(batch_idx, 0, 1)         # (K, M, B) ints
                xb_all = x[bidx_t]                             # (K, M, B, ...)
                yb_all = y[bidx_t]
                (chain_flat, qkey), (traj, grad_sq_traj) = walk(
                    chain_flat, (xb_all, yb_all), walk_mask, kbar0, qkey)
            agg_key = qkey
            if bits < 32:
                with jax.named_scope(scopes.AGGREGATE_QDQ):
                    _, agg_key = jax.random.split(qkey)
            return finalize(device_flat, traj, grad_sq_traj, walk_devices,
                            walk_mask, agg_rows, agg_weights, agg_devices, agg_key,
                            chain_flat, (xb_all[-1], yb_all[-1]))

        return round_fn

    # ----------------------------------------------- reference (seed) engine
    def _build_round_fn_reference(self, bits: int):
        cfg = self.cfg
        qcfg = dataclasses.replace(cfg.quant, bits=bits)
        model = self.model

        @functools.partial(jax.jit, static_argnames=())
        def round_fn(
            device_params,            # (n, ...)
            walk_devices,             # (M, K) int32
            walk_mask,                # (M, K) bool
            batch_idx,                # (M, K, B) int64 into global data
            agg_rows,                 # (A, n_agg) int32 neighbor ids per aggregator
            agg_weights,              # (A, n_agg) f32 (n_l/m, zero-padded)
            agg_devices,              # (A,) int32 aggregating device ids
            kbar0,                    # scalar int32: global step before round
            qkey,                     # PRNG key for quantization
        ):
            self._trace_count += 1
            x, y = self._x, self._y
            m, k = walk_devices.shape

            # Chain start models: w_{i^{t,0}}.
            chain_params = jax.tree_util.tree_map(
                lambda p: p[walk_devices[:, 0]], device_params
            )
            dev_last = device_params     # w_l^{t,last} buffer

            grad_fn = jax.grad(model.loss_fn)

            def one_chain_step(p, xb, yb, lr):
                g = grad_fn(p, (xb, yb))
                return jax.tree_util.tree_map(lambda pp, gg: pp - lr * gg, p, g), g

            def scan_body(carry, inputs):
                chain_params, dev_last, qkey = carry
                devs_k, mask_k, bidx_k, step_k = inputs
                lr = decreasing_lr(kbar0 + step_k + 1, cfg.lr_r, cfg.lr_q)
                xb = x[bidx_k]  # (M, B, ...)
                yb = y[bidx_k]
                new_params, grads = jax.vmap(one_chain_step, in_axes=(0, 0, 0, None))(
                    chain_params, xb, yb, lr
                )
                # Straggler mask: inactive chains keep their params.
                def mask_leaf(new, old):
                    mk = mask_k.reshape((m,) + (1,) * (new.ndim - 1))
                    return jnp.where(mk, new, old)

                stepped = jax.tree_util.tree_map(mask_leaf, new_params, chain_params)

                # QDFedRW: the hand-off to the next device transmits
                # Q(w^{k+1} - w^k); the received model is w^k + deq(Q(diff)).
                if qcfg.enabled:
                    qkey, sub = jax.random.split(qkey)

                    def quant_leaf(new, old, leaf_key):
                        diff = new - old
                        qd = dequantize(
                            quantize(diff, qcfg, leaf_key), dtype=new.dtype
                        )
                        return old + qd

                    leaves_new, treedef = jax.tree_util.tree_flatten(stepped)
                    leaves_old = jax.tree_util.tree_leaves(chain_params)
                    keys = jax.random.split(sub, len(leaves_new))
                    leaves_q = [
                        quant_leaf(ln, lo, kk)
                        for ln, lo, kk in zip(leaves_new, leaves_old, keys)
                    ]
                    stepped = jax.tree_util.tree_unflatten(treedef, leaves_q)

                # Scatter each (active) chain's params to its current device's
                # w^{t,last} slot; chain order breaks ties deterministically.
                def scatter_chain(c, buf):
                    def set_leaf(b, cp):
                        return jax.lax.cond(
                            mask_k[c],
                            lambda: b.at[devs_k[c]].set(cp[c]),
                            lambda: b,
                        )

                    return jax.tree_util.tree_map(
                        lambda b, cp: set_leaf(b, cp), buf, stepped
                    )

                dev_last = jax.lax.fori_loop(
                    0, m, lambda c, buf: scatter_chain(c, buf), dev_last
                )
                grad_sq = sum(
                    jnp.sum(g**2, axis=tuple(range(1, g.ndim)))
                    for g in jax.tree_util.tree_leaves(grads)
                )  # (M,)
                return (stepped, dev_last, qkey), grad_sq

            steps = jnp.arange(k, dtype=jnp.int32)
            (chain_params, dev_last, qkey), grad_sq_traj = jax.lax.scan(
                scan_body,
                (chain_params, dev_last, qkey),
                (walk_devices.T, walk_mask.T, jnp.swapaxes(batch_idx, 0, 1), steps),
            )

            gamma_hat = gamma_hat_from_traj(grad_sq_traj, walk_mask)

            # Decentralized aggregation (Eq. 11 / Eq. 14).
            if qcfg.enabled:
                qkey, sub = jax.random.split(qkey)

                def agg_leaf(buf, start_buf, leaf_key):
                    diffs = buf[agg_rows] - start_buf[agg_rows]  # (A, n_agg, ...)
                    flat = diffs.reshape((-1,) + diffs.shape[2:])
                    keys = jax.random.split(leaf_key, flat.shape[0])
                    qd = jax.vmap(lambda d, kk: dequantize(quantize(d, qcfg, kk)))(
                        flat, keys
                    ).reshape(diffs.shape)
                    w = agg_weights.reshape(agg_weights.shape + (1,) * (diffs.ndim - 2))
                    upd = jnp.sum(w * qd, axis=1)  # (A, ...)
                    base = start_buf[agg_devices]
                    return buf.at[agg_devices].set(base + upd, mode="drop")

                leaves_last, treedef = jax.tree_util.tree_flatten(dev_last)
                leaves_start = jax.tree_util.tree_leaves(device_params)
                keys = jax.random.split(sub, len(leaves_last))
                new_leaves = [
                    agg_leaf(bl, bs, kk)
                    for bl, bs, kk in zip(leaves_last, leaves_start, keys)
                ]
                new_device_params = jax.tree_util.tree_unflatten(treedef, new_leaves)
            else:

                def agg_leaf(buf):
                    gathered = buf[agg_rows]  # (A, n_agg, ...)
                    w = agg_weights.reshape(
                        agg_weights.shape + (1,) * (gathered.ndim - 2)
                    )
                    avg = jnp.sum(w * gathered, axis=1)
                    return buf.at[agg_devices].set(avg, mode="drop")

                new_device_params = jax.tree_util.tree_map(agg_leaf, dev_last)

            # Mean train loss over the round's final chain models, on their
            # last batch (cheap monitoring signal).
            last_x = x[batch_idx[:, -1]]
            last_y = y[batch_idx[:, -1]]
            losses = jax.vmap(model.loss_fn)(chain_params, (last_x, last_y))
            return new_device_params, jnp.mean(losses), gamma_hat

        return round_fn

    # ------------------------------------------------------------- host side
    def _plan_round(self, state: DFedRWState) -> tuple[WalkPlan, np.ndarray, tuple]:
        plan, bidx = self.plan_walks(state)
        agg = self.plan_aggregation(plan)
        return plan, bidx, agg

    def plan_walks(
        self, state: DFedRWState, topo: Topology | None = None,
        m: int | None = None,
    ) -> tuple[WalkPlan, np.ndarray]:
        """Sample the round's M walk trajectories plus their per-step batch
        indices (one protocol-rng draw order shared by every engine and by
        the virtual-time simulator — repro.sim truncates the returned plan
        before building the aggregation plan). ``topo`` overrides the bound
        topology (time-varying graphs); ``m`` overrides the chain count —
        the fully-asynchronous simulator samples fresh chains only into the
        slots freed at the last trigger, so a partially-busy window plans
        fewer than ``cfg.m_chains`` walks (m=None keeps the config count and
        the draw order the synchronous engine uses)."""
        cfg, rng = self.cfg, self.rng
        topo = self.topo if topo is None else topo
        m_chains = cfg.m_chains if m is None else int(m)
        assert m is None or not cfg.chain_mode, \
            "chain_mode chains persist by construction; partial refills are undefined"
        plan = sample_walks(
            topo,
            m_chains,
            cfg.k_walk,
            rng,
            straggler=cfg.straggler,
            start_devices=state.chain_starts if cfg.chain_mode else None,
        )
        # Per-step batches from the visited device's local data. A slow device
        # contributes a *partial* update (paper Table II row 4): it processes
        # only batch_size/slowdown distinct samples within the global clock
        # (realized by tiling a sub-batch, i.e. an unbiased smaller-batch
        # gradient at unchanged shapes). One rng draw for the whole (M*K, B)
        # column tensor; the dense (n, max_size) client index matrix turns it
        # into global sample ids by fancy indexing.
        slow = cfg.straggler.slow_mask(topo.n)
        b_slow = max(1, int(cfg.batch_size / max(cfg.straggler.slowdown, 1.0)))
        flat_dev = plan.devices.reshape(-1)                       # (M*K,)
        idx_mat = self.data.client_idx                            # (n, max_size)
        cols = rng.integers(0, idx_mat.shape[1], size=(flat_dev.shape[0], cfg.batch_size))
        bidx = idx_mat[flat_dev[:, None], cols]
        if cfg.straggler.mode == "partial" and slow.any():
            reps = int(np.ceil(cfg.batch_size / b_slow))
            sub = idx_mat[flat_dev[:, None], cols[:, :b_slow]]
            tiled = np.tile(sub, (1, reps))[:, : cfg.batch_size]
            bidx = np.where(slow[flat_dev][:, None], tiled, bidx)
        bidx = bidx.reshape(m_chains, cfg.k_walk, cfg.batch_size)
        return plan, bidx

    def plan_aggregation(
        self, plan: WalkPlan, topo: Topology | None = None
    ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Build the round's (agg_devices, agg_rows, agg_weights) from the
        (possibly deadline-truncated) walk plan. Shapes are padded to fixed
        sizes (pad slots use device id >= n and zero weight; the jitted
        scatter drops them) so the round function compiles exactly once per
        config."""
        cfg, rng = self.cfg, self.rng
        topo = self.topo if topo is None else topo
        participants = np.unique(plan.devices[plan.mask])
        sizes = self.data.client_sizes
        if cfg.chain_mode:
            # §VI-F: N_A(i) = the other chains' end devices; aggregators are
            # exactly the (unique) chain-end devices, padded to M rows.
            # Zero-length chains (deadline/churn truncation to k_m = 0, or a
            # dropped straggler — never produced by the synchronous planner,
            # which floors k_m at 1) performed no step: their "end" device is
            # just the start device holding stale params, so they neither
            # aggregate nor contribute (zero weight).
            alive = plan.k_m > 0
            agg_devices = np.unique(plan.last_device[alive])
            rows = np.tile(plan.last_device, (len(agg_devices), 1))
            w = sizes[plan.last_device].astype(np.float64) * alive
            wsum = w.sum()
            weights = np.tile(w / (wsum if wsum > 0 else 1.0),
                              (len(agg_devices), 1))
            pad = cfg.m_chains - len(agg_devices)
            if pad > 0:
                # Distinct out-of-range ids so the jitted scatter can keep
                # its unique-indices fast path (all pad slots are dropped).
                agg_devices = np.concatenate([agg_devices, topo.n + np.arange(pad)])
                rows = np.pad(rows, ((0, pad), (0, 0)))
                weights = np.pad(weights, ((0, pad), (0, 0)))
        elif getattr(topo, "transition", None) is None:
            # Implicit SparseTopology: same aggregation law as the dense
            # branch below (uniform aggregator draw; per aggregator a uniform
            # random subset of <= n_agg participating neighbors in uniform
            # random order; size-weights normalized over the selection; pads
            # carry the aggregator's own id and zero weight) realized as one
            # CSR gather + lexsort instead of a per-aggregator Python loop.
            # RNG consumption differs from the dense branch — the two
            # representations are distinct planners, not stream twins.
            n_aggregators = max(1, int(round(topo.n * cfg.agg_fraction)))
            agg_devices = rng.choice(topo.n, size=n_aggregators, replace=False)
            n_agg = cfg.n_agg
            deg = topo.degrees[agg_devices]
            total = int(deg.sum())
            starts = np.cumsum(deg) - deg
            offs = np.arange(total, dtype=np.int64) - np.repeat(starts, deg)
            flat = topo.indices[np.repeat(topo.indptr[agg_devices], deg) + offs]
            row_id = np.repeat(np.arange(n_aggregators, dtype=np.int64), deg)
            # The aggregator itself is always a candidate (include_self=True).
            flat = np.concatenate([flat, agg_devices])
            row_id = np.concatenate(
                [row_id, np.arange(n_aggregators, dtype=np.int64)])
            is_part = np.zeros(topo.n, dtype=bool)
            is_part[participants] = True
            keep = is_part[flat] | (flat == agg_devices[row_id])
            flat, row_id = flat[keep], row_id[keep]
            keys = rng.random(flat.shape[0])
            order = np.lexsort((keys, row_id))
            flat, row_id = flat[order], row_id[order]
            row_start = np.searchsorted(row_id, np.arange(n_aggregators))
            rank = np.arange(flat.shape[0], dtype=np.int64) - row_start[row_id]
            sel = rank < n_agg
            s_row, s_dev, s_rank = row_id[sel], flat[sel], rank[sel]
            rows = np.tile(agg_devices[:, None], (1, n_agg))
            weights = np.zeros((n_aggregators, n_agg), dtype=np.float64)
            rows[s_row, s_rank] = s_dev
            w_flat = sizes[s_dev].astype(np.float64)
            wsum = np.bincount(s_row, weights=w_flat, minlength=n_aggregators)
            weights[s_row, s_rank] = w_flat / np.maximum(wsum, 1.0)[s_row]
        else:
            n_aggregators = max(1, int(round(topo.n * cfg.agg_fraction)))
            agg_devices = rng.choice(topo.n, size=n_aggregators, replace=False)
            n_agg = cfg.n_agg
            row_list, weight_list = [], []
            part_set = set(participants.tolist())
            for i in agg_devices:
                nbrs = [j for j in topo.neighbors(i, include_self=True)
                        if j in part_set or j == i]
                rng.shuffle(nbrs)
                nbrs = np.array(nbrs[:n_agg], dtype=np.int64)
                pad = n_agg - len(nbrs)
                w = sizes[nbrs].astype(np.float64)
                w = w / max(w.sum(), 1.0)
                if pad > 0:
                    nbrs = np.pad(nbrs, (0, pad), constant_values=i)
                    w = np.pad(w, (0, pad))
                row_list.append(nbrs)
                weight_list.append(w)
            rows = np.stack(row_list)
            weights = np.stack(weight_list)
        agg_rows = rows.astype(np.int32)
        agg_w = weights.astype(np.float32)
        return (agg_devices.astype(np.int32), agg_rows, agg_w)

    def _comm_cost_bits(
        self, plan: WalkPlan, agg: tuple, d_params: int,
        bits: int | None = None,
    ) -> tuple[float, float]:
        """Eq. 18 comm accounting (vectorized: one bincount over hop edges and
        one over aggregation sends). Returns (total_bits, busiest_device_bits).
        ``bits`` prices the round at a non-default width (adaptive control)."""
        bits = self.cfg.quant.bits if bits is None else int(bits)
        hop_bits = wire_bits(d_params, bits)
        n = self.topo.n
        # Walk hand-offs: each cross-device hop sends params (or quantized
        # diff); the sender pays (send side). Edge (k-1 -> k) exists when
        # step k executed — mask-driven, so the asynchronous simulator's
        # window views charge a hop in the window its *destination* step
        # runs (an in-flight hand-off at a trigger is billed on arrival,
        # through the resumed chain's masked anchor column). For the
        # synchronous planner's prefix masks this is exactly
        # "step k+1 inside the realized length K_m".
        src = plan.devices[:, :-1]
        dst = plan.devices[:, 1:]
        live = plan.mask[:, 1:] & (src != dst)
        per_dev = np.bincount(src[live].ravel(), minlength=n).astype(np.float64)
        # Aggregation: each participating device l sends its (quantized diff)
        # model to the aggregators that list it.
        agg_devices, agg_rows, agg_w = agg
        sends = (agg_w > 0) & (agg_rows != agg_devices[:, None])
        per_dev += np.bincount(agg_rows[sends].ravel(), minlength=n)
        per_dev *= hop_bits
        return float(per_dev.sum()), float(per_dev.max())

    # ------------------------------------------------------------------- run
    def run_round(self, state: DFedRWState, key: jax.Array) -> tuple[DFedRWState, RoundMetrics]:
        with (_no_span if self.obs is None else self.obs.span)(scopes.ENGINE_PLAN):
            plan, bidx, agg = self._plan_round(state)
        return self.execute_round(state, plan, bidx, agg, key)

    def round_inputs(self, state: DFedRWState, plan: WalkPlan,
                     bidx: np.ndarray, agg: tuple, key: jax.Array) -> tuple:
        """The round program's arguments for one planned round."""
        agg_devices, agg_rows, agg_w = agg
        return (state.device_params, jnp.asarray(plan.devices),
                jnp.asarray(plan.mask), jnp.asarray(bidx),
                jnp.asarray(agg_rows), jnp.asarray(agg_w),
                jnp.asarray(agg_devices), jnp.int32(state.global_step), key)

    def execute_round(
        self,
        state: DFedRWState,
        plan: WalkPlan,
        bidx: np.ndarray,
        agg: tuple,
        key: jax.Array,
        account_plan: WalkPlan | None = None,
        bits: int | None = None,
    ) -> tuple[DFedRWState, RoundMetrics]:
        """Run one planned round through the jitted engine and update the
        protocol state. ``plan`` may be a (deadline/churn-)truncated version
        of the sampled plan; ``account_plan`` optionally charges Eq. 18 comm
        for a different plan than the one computed (the drop-stragglers
        baseline pays for hops whose updates it then discards); ``bits``
        selects the round's wire bit-width from the per-width program table
        (None = the static config width) — compute AND Eq. 18 pricing both
        follow it."""
        cfg = self.cfg
        obs = self.obs
        span = _no_span if obs is None else obs.span
        bits_eff = cfg.quant.bits if bits is None else int(bits)
        round_fn = self.round_program(bits_eff)
        agg_devices = agg[0]
        with span(scopes.ENGINE_EXECUTE):
            with span(scopes.ENGINE_DISPATCH):
                new_params, loss, gamma_hat = round_fn(
                    *self.round_inputs(state, plan, bidx, agg, key))
            with span(scopes.ENGINE_ACCOUNT):
                self._programs_run.add(bits_eff)
                retraces = self.retrace_count
                if retraces > self._retraces_warned:
                    # Re-armed: every NEW retrace warns again (a monotone
                    # counter, not a fire-once latch — a second unstable
                    # shape is still reported).
                    warnings.warn(
                        f"DFedRW round function retraced ({retraces} retrace(s) so "
                        f"far); a plan shape is not stable across rounds (this "
                        f"forfeits compiled-executable reuse)",
                        stacklevel=2,
                    )
                    self._retraces_warned = retraces
                acct = plan if account_plan is None else account_plan
                tot, busiest = self._comm_cost_bits(acct, agg, self.flat_spec.d, bits=bits_eff)
                updated = (state.updated.copy() if state.updated is not None
                           else np.zeros(self.topo.n, dtype=bool))
                walked = np.unique(plan.devices[plan.mask])
                aggregated = agg_devices[agg_devices < self.topo.n]
                updated[walked] = True
                updated[aggregated] = True
                new_state = DFedRWState(
                    device_params=new_params,
                    round=state.round + 1,
                    global_step=state.global_step + cfg.k_walk,
                    chain_starts=plan.last_device if cfg.chain_mode else None,
                    comm_bits_total=state.comm_bits_total + tot,
                    comm_bits_busiest=state.comm_bits_busiest + busiest,
                    updated=updated,
                )
            with span(scopes.ENGINE_WAIT):
                metrics = RoundMetrics(
                    round=new_state.round,
                    train_loss=float(loss),
                    comm_bits_round=tot,
                    comm_bits_busiest_round=busiest,
                    gamma_hat=float(gamma_hat),
                )
        if obs is not None:
            obs.counter("engine/rounds")
            obs.counter("engine/programs", 1, bits=bits_eff)
            obs.counter("engine/comm_bits", tot, bits=bits_eff)
            obs.counter("engine/comm_bits_busiest", busiest)
            obs.counter("engine/steps_executed", int(plan.mask.sum()))
            # the row merge's passes: one at bits < 32, the walk's rows then
            # the aggregators' at 32
            passes = ((np.union1d(walked, aggregated),) if bits_eff < 32
                      else (walked, aggregated))
            obs.counter("engine/merge_rows", sum(p.size for p in passes))
            obs.counter("engine/merge_groups",
                        sum(np.unique(p // ROW_GROUP).size for p in passes))
            if retraces > self._retraces_obs:
                obs.counter("engine/retraces", retraces - self._retraces_obs)
                self._retraces_obs = retraces
            obs.flush()
        return new_state, metrics

    # ------------------------------------------------------------- evaluate
    def evaluate(self, state: DFedRWState, x_test, y_test, max_batch: int = 2048) -> dict:
        """Accuracy/loss of the average over *participating* device models
        (the paper evaluates the learned global model on the IID test set;
        devices that never trained/aggregated still hold their random init
        and are not part of the learned model)."""
        if state.updated is not None and state.updated.any():
            sel = jnp.asarray(np.nonzero(state.updated)[0])
        else:
            sel = jnp.arange(self.topo.n)
        if self.cfg.engine == "flat":
            mean_params = unflatten_tree(
                jnp.mean(state.device_params[sel], axis=0), self.flat_spec
            )
        else:
            mean_params = jax.tree_util.tree_map(
                lambda p: jnp.mean(p[sel], axis=0), state.device_params
            )
        x_test = jnp.asarray(x_test[:max_batch])
        y_test = jnp.asarray(y_test[:max_batch])
        logits = self.model.predict(mean_params, x_test)
        acc = jnp.mean(jnp.argmax(logits, -1) == y_test)
        loss = self.model.loss_fn(mean_params, (x_test, y_test))
        return {"accuracy": float(acc), "loss": float(loss)}
