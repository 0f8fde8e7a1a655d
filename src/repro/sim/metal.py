"""Trace-driven metal execution: replay a recorded ``SimTrace`` on devices.

``repro.sim`` proves its claims on a virtual clock; this module closes the
sim-to-metal loop by executing the *same* recorded schedule on live JAX
devices and holding the result to the simulator's trajectory:

  * ``SimTrace.schedule()`` compiles the trace into per-window
    :class:`~repro.sim.trace.WindowSchedule` plans (fixed shapes, resolved
    bit-widths, cumulative lr step counts).
  * :class:`MetalReplay` drives each window through real devices: the M
    chain walks are sharded over a device mesh (``shard_map`` over a
    ``"chains"`` axis — single-process multi-device is the CI fallback,
    ``launch/replay.py`` adds localhost multi-process on top via an
    :class:`Exchange`), then the engine's own finalize runs replicated:
    winner election, `merge_rows` row writes and Eq. 11/14 aggregation.
  * :class:`FaultInjector` re-derives the executed-step masks and dead
    aggregators from the trace's raw fault timeline (completion timestamps,
    churn kills, straggler deficits) instead of trusting the recorded
    masks — verifying that a live deployment subjected to the same stalls
    and drops degrades to the same partial aggregation the sim computed.

Conformance contract (tests/test_metal_conformance.py): at fp32 the metal
trajectory is **bit-exact** against ``AsyncDFedRW.replay`` — the per-chain
walk math is closed under chain slicing (each chain's scan only reads its
own row; XLA executes the identical scalar graph per row regardless of how
many rows share a program), and the finalize runs replicated on the full
trajectory, so device count and process count cannot change a bit. At
bits<32 the stochastic quantizer draws per-shard keys (``fold_in`` by mesh
position), so metal is held to *quantization tolerance*: the sim's own
replay spread under a different root key bounds the allowed deviation.

Why not one cross-process XLA computation: jaxlib's CPU backend does not
implement multi-process computations ("Multiprocess computations aren't
implemented on the CPU backend"), and — more to the point — a real DFedRW
fleet is not one SPMD program: devices exchange *messages*. The
:class:`Exchange` seam models exactly that (per-process compiled compute +
explicit trajectory exchange), which is what makes the localhost
deployment a faithful miniature of the paper's setting.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Iterable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.dfedrw import DFedRW, DFedRWState, RoundMetrics
from repro.core.metrics import History
from repro.core.walk import WalkPlan
from repro.sim.trace import (
    TRACE_SHAPE_KEYS,
    SimTrace,
    TraceIntegrityError,
    WindowSchedule,
)

__all__ = [
    "Exchange",
    "LocalExchange",
    "FaultInjector",
    "MetalConformanceError",
    "MetalReplay",
    "MetalResult",
    "conformance_diff",
]


class MetalConformanceError(RuntimeError):
    """The live execution diverged from the recorded schedule: a re-derived
    fault mask disagrees with the sim's, shards disagree with each other,
    or two trajectories that must match do not."""


# --------------------------------------------------------------------- comms
class Exchange:
    """Trajectory transport between the processes of a deployment.

    One deployment = ``n_shards`` processes, each computing a contiguous
    slice of the M chains; after the walk phase every process contributes
    its slice and receives everyone's (all-gather), then runs the identical
    replicated finalize. ``launch/replay.py`` provides the TCP socket
    implementation; tests and single-process runs use
    :class:`LocalExchange`.
    """

    n_shards: int = 1
    shard_id: int = 0

    def allgather(self, payload: Any) -> list:
        """Contribute this shard's payload; return all shards' payloads
        ordered by shard id (ours included)."""
        raise NotImplementedError

    def close(self) -> None:  # pragma: no cover - transport-specific
        pass


class LocalExchange(Exchange):
    """Single-process deployment: the all-gather is the identity."""

    n_shards = 1
    shard_id = 0

    def allgather(self, payload: Any) -> list:
        return [payload]


# ------------------------------------------------------------ fault injector
@dataclasses.dataclass
class FaultInjector:
    """Re-derive the sim's churn/straggler degradation from raw fault
    signals and (optionally) act it out in real time.

    The recorded ``exec_mask`` is the sim's *conclusion*; the injector
    recomputes it from the fault *evidence* the trace also carries — which
    steps have finite completion timestamps, which chains the churn model
    killed, which fell short of their planned length — and raises
    :class:`MetalConformanceError` if the live derivation disagrees with
    the recording. That closes the Eq. 11/14 loop: the metal side proves it
    reaches the same partial aggregation from the same faults, rather than
    replaying an answer.

    ``policy`` mirrors ``SimConfig.policy``: ``"partial"``/``"overlap"``
    aggregate whatever executed; ``"drop"`` discards any chain that did not
    finish its planned walk. ``stall_scale`` > 0 additionally sleeps
    ``stall_scale`` wall-seconds per missing step, turning the recorded
    straggler deficit into an actual process stall (off by default so test
    suites stay fast)."""

    policy: str = "partial"
    stall_scale: float = 0.0
    verify: bool = True
    stalls_injected: int = 0
    steps_stalled: int = 0
    aggregators_dropped: int = 0

    def derive_exec_mask(self, w: WindowSchedule) -> np.ndarray:
        """(M, K) bool — the steps a live fleet under the recorded fault
        timeline would aggregate: planned steps whose completion instant
        exists, minus (under ``drop``) every stalled chain entirely."""
        derived = np.asarray(w.account_mask) & np.isfinite(
            np.asarray(w.timestamps))
        if self.policy == "drop":
            derived = derived & ~np.asarray(w.stalled)[:, None]
        return derived

    def inject(self, w: WindowSchedule) -> np.ndarray:
        """Derive, verify against the recording, act out the stalls; returns
        the exec mask the window must run with."""
        derived = self.derive_exec_mask(w)
        if self.verify:
            recorded = np.asarray(w.exec_mask)
            if not np.array_equal(derived, recorded):
                bad = np.nonzero((derived != recorded).any(axis=1))[0]
                raise MetalConformanceError(
                    f"window round={w.round}: fault-derived exec mask "
                    f"disagrees with the recorded one on chain(s) "
                    f"{bad.tolist()} (policy={self.policy!r}) — the live "
                    f"degradation does not reproduce the sim's Eq. 11/14 "
                    f"partial aggregation")
        stalled = np.asarray(w.stalled)
        deficit = int(np.maximum(
            np.asarray(w.k_planned) - np.asarray(w.k_done), 0).sum())
        self.stalls_injected += int(stalled.sum())
        self.steps_stalled += deficit
        self.aggregators_dropped += int(w.dead_aggregators.size)
        if self.stall_scale > 0.0 and deficit:
            time.sleep(self.stall_scale * deficit)
        return derived


# ---------------------------------------------------------------- the result
@dataclasses.dataclass
class MetalResult:
    """What a metal replay produced (mirrors ``SimResult`` where the two
    overlap, so conformance checks compare like with like)."""

    history: History
    records: list
    state: DFedRWState
    virtual_time_s: float = 0.0
    windows: int = 0
    n_shards: int = 1
    fault: FaultInjector | None = None

    @property
    def device_matrix(self) -> np.ndarray:
        return np.asarray(self.state.device_params)


def conformance_diff(a: Any, b: Any) -> float:
    """Max abs elementwise difference between two device matrices (accepts
    ``DFedRWState``/``MetalResult``/``SimResult``-likes or raw arrays).
    0.0 means bit-exact at fp32."""
    pa = getattr(a, "state", a)
    pb = getattr(b, "state", b)
    pa = np.asarray(getattr(pa, "device_params", pa), dtype=np.float64)
    pb = np.asarray(getattr(pb, "device_params", pb), dtype=np.float64)
    if pa.shape != pb.shape:
        raise MetalConformanceError(
            f"device matrices disagree in shape: {pa.shape} vs {pb.shape}")
    return float(np.max(np.abs(pa - pb))) if pa.size else 0.0


# ---------------------------------------------------------------- the runner
class MetalReplay:
    """Execute a recorded schedule on live devices.

    Wraps a :class:`~repro.core.dfedrw.DFedRW` engine (flat only) for its
    spec, data binding, Eq. 18 pricing and evaluation, and runs the two
    halves of its round program (`DFedRW.round_parts`) apart: the engine's
    walk as a ``shard_map`` over a ``"chains"`` mesh axis of this process's
    devices, and the engine's finalize (winner election, `merge_rows`
    writes, aggregation) replicated on the exchanged trajectory, so every
    shard deterministically computes the same new device matrix.

    ``exchange`` splits the M chains across processes
    (``launch/replay.py``); the default :class:`LocalExchange` runs all
    chains here. ``devices`` pins the local mesh (default: the largest
    divisor-of-M prefix of ``jax.local_devices()``, so M=5 chains on 8
    virtual devices use 5 of them and no padding is ever needed).
    """

    def __init__(
        self,
        engine: DFedRW,
        *,
        exchange: Exchange | None = None,
        devices: list | None = None,
    ):
        if engine.cfg.engine != "flat":
            raise ValueError("MetalReplay drives the flat engine only")
        self.engine = engine
        self.exchange = exchange if exchange is not None else LocalExchange()
        self._devices = devices
        self.t = 0.0                      # virtual clock (schedule time)
        self.obs = None
        self._walk_fns: dict[tuple, Any] = {}
        self._finalize_fns: dict[int, Any] = {}
        self._mesh_axis_used = 0

    # ----------------------------------------------------------- telemetry
    def attach_obs(self, rec) -> None:
        """Attach a ``repro.obs.Recorder``; an unbound ``VirtualClock``
        binds to the *schedule's* virtual time, so the metal stream is
        priced on the same clock as the sim stream it is diffed against
        (tools/obs_diff.py is the sim-vs-metal gate)."""
        from repro.obs import VirtualClock
        self.obs = rec
        if isinstance(rec.clock, VirtualClock) and not rec.clock.bound:
            rec.clock.bind(lambda: self.t)

    # ------------------------------------------------------------ programs
    def _local_mesh(self, m_local: int):
        from repro.launch.mesh import make_chains_mesh
        devs = self._devices if self._devices is not None \
            else jax.local_devices()
        mesh = make_chains_mesh(devs, m_local)
        self._mesh_axis_used = max(self._mesh_axis_used, mesh.size)
        return mesh

    def _walk_fn(self, bits: int, m_local: int):
        """Compiled walk program for (wire width, shard chain count): the
        engine's walk (`DFedRW.round_parts`) — Eq. 10 masked steps at the
        globally decreasing lr, Eq. 13 quantized hand-offs when bits<32 —
        over this shard's rows only."""
        fn = self._walk_fns.get((bits, m_local))
        if fn is not None:
            return fn
        from jax.sharding import PartitionSpec as P

        walk, _ = self.engine.round_parts(bits)
        mesh = self._local_mesh(m_local)
        sharded = len(mesh.devices) > 1

        def body(x, y, chain_flat, mask, bidx, kbar0, qkey):
            if bits < 32:
                # Distinct stream per mesh position: a valid stochastic
                # quantizer, a different draw order than the sim — this is
                # the source of the bits<32 tolerance band.
                shard_ix = jax.lax.axis_index("chains") if sharded else 0
                qkey = jax.random.fold_in(qkey, shard_ix)
            bidx_t = jnp.swapaxes(bidx, 0, 1)          # (K, mb, B)
            _, (traj, grad_sq) = walk(
                chain_flat, (x[bidx_t], y[bidx_t]), mask, kbar0, qkey)
            return traj, grad_sq                       # (K, mb, d) / (K, mb)

        if sharded:
            fn = jax.jit(jax.shard_map(
                body, mesh=mesh,
                in_specs=(P(), P(), P("chains"), P("chains"), P("chains"),
                          P(), P()),
                out_specs=(P(None, "chains"), P(None, "chains")),
                check_vma=False))
        else:
            fn = jax.jit(body)
        self._walk_fns[(bits, m_local)] = fn
        return fn

    def _finalize_fn(self, bits: int):
        """Replicated finalize: the engine's own finalize (winner election,
        `merge_rows` writes, Eq. 11 / Eq. 14 aggregation, loss) over the
        full gathered (K, M, d) trajectory — the ops of the tail of
        ``DFedRW.round_program``, which is what makes metal bit-exact at
        fp32 regardless of how the walk was sharded."""
        fn = self._finalize_fns.get(bits)
        if fn is None:
            fn = self._finalize_fns[bits] = jax.jit(self.engine.round_parts(bits)[1])
        return fn

    # ----------------------------------------------------------- execution
    def _check_trace(self, trace: SimTrace) -> None:
        h, cfg = trace.header, self.engine.cfg
        expect = dict(n=self.engine.topo.n, m_chains=cfg.m_chains,
                      k_walk=cfg.k_walk, batch_size=cfg.batch_size,
                      bits=cfg.quant.bits)
        mismatched = {k: (h.get(k), v) for k, v in expect.items()
                      if h.get(k) != v}
        if mismatched:
            detail = "; ".join(f"{k}: trace={hv} engine={ev}"
                               for k, (hv, ev) in mismatched.items())
            raise TraceIntegrityError(
                f"trace header does not match this engine ({detail}); "
                f"metal replay needs the recording configuration "
                f"(header keys {TRACE_SHAPE_KEYS})")

    def _shard_slice(self, m: int) -> np.ndarray:
        return np.array_split(np.arange(m), self.exchange.n_shards)[
            self.exchange.shard_id]

    def run_window(
        self, state: DFedRWState, w: WindowSchedule, key: jax.Array,
        fault: FaultInjector | None = None,
    ) -> tuple[DFedRWState, RoundMetrics]:
        """One window: shard-local walk, trajectory exchange, replicated
        finalize, Eq. 18 pricing — the metal twin of
        ``DFedRW.execute_round`` driving the recorded plans."""
        engine, cfg = self.engine, self.engine.cfg
        m, k = w.devices.shape
        exec_mask = w.exec_mask if fault is None else fault.inject(w)
        sub = key                        # the per-window key (same split
                                         # discipline as the sim's _drive)

        rows = self._shard_slice(m)
        if rows.size:
            dev_np = np.asarray(state.device_params)
            chain0 = jnp.asarray(dev_np[w.devices[rows, 0]])
            walk = self._walk_fn(w.bits, int(rows.size))
            traj_loc, gsq_loc = walk(
                jnp.asarray(engine._x), jnp.asarray(engine._y), chain0,
                jnp.asarray(exec_mask[rows]), jnp.asarray(w.bidx[rows]),
                jnp.int32(w.kbar0), sub)
            payload = (np.asarray(traj_loc), np.asarray(gsq_loc))
        else:                              # more processes than chains
            d_pad = engine.flat_spec.d_pad
            payload = (np.zeros((k, 0, d_pad), dtype=np.float32),
                       np.zeros((k, 0), dtype=np.float32))
        parts = self.exchange.allgather(payload)
        traj = jnp.asarray(np.concatenate([p[0] for p in parts], axis=1))
        grad_sq = jnp.asarray(np.concatenate([p[1] for p in parts], axis=1))
        if traj.shape[1] != m:
            raise MetalConformanceError(
                f"exchange returned {traj.shape[1]} chains, schedule has {m}")

        agg_key = jax.random.fold_in(sub, 4096)  # off the shard-key range
        last_bidx = jnp.asarray(w.bidx[:, -1])
        finalize = self._finalize_fn(w.bits)
        new_params, loss, gamma_hat = finalize(
            state.device_params, traj, grad_sq,
            jnp.asarray(w.devices), jnp.asarray(exec_mask),
            jnp.asarray(w.agg_rows), jnp.asarray(w.agg_weights),
            jnp.asarray(w.agg_devices), agg_key,
            traj[-1], (engine._x[last_bidx], engine._y[last_bidx]))

        account_plan = WalkPlan(
            devices=w.devices, mask=w.account_mask,
            k_m=w.account_mask.sum(axis=1).astype(np.int32),
            timestamps=w.timestamps)
        agg = (w.agg_devices, w.agg_rows, w.agg_weights)
        tot, busiest = engine._comm_cost_bits(
            account_plan, agg, engine.flat_spec.d, bits=w.bits)
        updated = (state.updated.copy() if state.updated is not None
                   else np.zeros(engine.topo.n, dtype=bool))
        updated[np.unique(w.devices[exec_mask])] = True
        updated[w.agg_devices[w.agg_devices < engine.topo.n]] = True
        new_state = DFedRWState(
            device_params=new_params,
            round=state.round + 1,
            global_step=state.global_step + cfg.k_walk,
            chain_starts=None,
            comm_bits_total=state.comm_bits_total + tot,
            comm_bits_busiest=state.comm_bits_busiest + busiest,
            updated=updated,
        )
        metrics = RoundMetrics(
            round=new_state.round, train_loss=float(loss),
            comm_bits_round=tot, comm_bits_busiest_round=busiest,
            gamma_hat=float(gamma_hat))
        return new_state, metrics

    def _obs_window(self, w: WindowSchedule, exec_mask: np.ndarray,
                    metrics: RoundMetrics) -> None:
        """Metal-side telemetry, series-for-series the sim's emission
        (``DFedRW.execute_round`` + ``AsyncDFedRW._obs_window``) priced on
        the schedule's virtual clock — so ``tools/obs_diff.py`` between a
        sim stream and a metal stream of the same trace is clean. Uplink
        contention series are sim-only (the metal side has no modeled
        uplink) and surface as diff *notes*, never failures."""
        obs = self.obs
        obs.record_span("engine/execute_round", w.t_end, w.t_end)
        obs.counter("engine/rounds")
        obs.counter("engine/programs", 1, bits=w.bits)
        obs.counter("engine/comm_bits", metrics.comm_bits_round, bits=w.bits)
        obs.counter("engine/comm_bits_busiest",
                    metrics.comm_bits_busiest_round)
        obs.counter("engine/steps_executed", int(exec_mask.sum()))
        obs.flush()
        from repro.sim.runner import SimRoundRecord
        record = SimRoundRecord(
            round=w.round, t_start=w.t_start,
            t_compute_end=w.t_compute_end, t_end=w.t_end, events=w.events,
            host_loop_s=0.0, k_planned=w.k_planned, k_done=w.k_done,
            k_exec=exec_mask.sum(axis=1).astype(np.int32), killed=w.killed,
            agg_latency_s=w.t_end - w.t_compute_end, resumed=w.resumed,
            bits=w.bits)
        obs.record_span("sim/window", record.t_start, record.t_end)
        obs.record_span("sim/walk", record.t_start, record.t_compute_end)
        obs.record_span("sim/aggregate", record.t_compute_end, record.t_end)
        obs.counter("sim/windows")
        obs.counter("sim/events", record.events)
        obs.counter("sim/chains_resumed", record.resumed_chains)
        obs.counter("sim/chains_truncated", record.truncated_chains)
        obs.counter("sim/chains_dropped", record.dropped_chains)
        obs.counter("sim/chains_killed", int(record.killed.sum()))
        obs.histogram("sim/window_steps", record.k_exec)
        obs.gauge("sim/bits", float(w.bits))
        obs.gauge("sim/queue_pressure", 0.0)
        obs.flush(t=record.t_end)

    def run(
        self,
        trace: SimTrace | Iterable[WindowSchedule],
        key: jax.Array,
        x_test: np.ndarray | None = None,
        y_test: np.ndarray | None = None,
        eval_every: int = 1,
        fault: FaultInjector | None = None,
        callback: Callable | None = None,
    ) -> MetalResult:
        """Execute the whole schedule. Same root ``key`` and key-split
        discipline as ``AsyncDFedRW.replay``/``run`` (init from the root,
        one split per window), so at fp32 the resulting ``state`` is
        bit-identical to the sim's."""
        if isinstance(trace, SimTrace):
            self._check_trace(trace)
            sched = trace.schedule()
        else:
            sched = list(trace)
        self.t = 0.0
        state = self.engine.init_state(key)
        hist = History()
        records: list[RoundMetrics] = []
        for r, w in enumerate(sched):
            if w.n != self.engine.topo.n:
                raise TraceIntegrityError(
                    f"window round={w.round}: schedule n={w.n} does not "
                    f"match engine n={self.engine.topo.n}")
            key, sub = jax.random.split(key)
            exec_mask = np.asarray(
                w.exec_mask if fault is None else fault.derive_exec_mask(w))
            state, metrics = self.run_window(state, w, sub, fault=fault)
            self.t = w.t_end
            records.append(metrics)
            if self.obs is not None:
                self._obs_window(w, exec_mask, metrics)
            if x_test is not None and ((r + 1) % eval_every == 0
                                       or r == len(sched) - 1):
                evald = self.engine.evaluate(state, x_test, y_test)
                hist.record(metrics, evald, state)
                if callback is not None:
                    callback(r, metrics, evald, w)
        return MetalResult(
            history=hist, records=records, state=state,
            virtual_time_s=self.t, windows=len(sched),
            n_shards=self.exchange.n_shards, fault=fault)
