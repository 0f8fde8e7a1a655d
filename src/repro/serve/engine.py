"""Continuous-batching serve engine over the slot-based ring-buffer cache.

One `ServeEngine` owns a decode cache with ``max_concurrency`` slots (the
batch dim of `T.init_cache`) and runs a step loop in which every engine
step is exactly one device program:

* **gang prefill step** — when prefilling slots outnumber decoding ones
  (admission waves, cold start — the low-occupancy regime where filling
  fast matters), one `make_prefill_step` call advances *every* prefilling
  slot by up to ``chunk`` tokens, writing k/v (or recurrent state) at
  each slot's own offset; slots that finish their prompt get their first
  token sampled from the same call's logits.
* **decode step** — otherwise one `make_serve_step(slots=True)` call
  decodes every in-flight slot at its own position, and the few
  prefilling slots (trickled admissions) *piggyback* on it, streaming
  their next prompt token at their own position: the fixed-shape chunk
  program would cost every decoding neighbour a stall plus
  (rows × chunk) wasted compute, while piggybacking fills an otherwise
  idle row for free. Retired and free rows ride along under an
  ``active`` mask that drops their cache writes, so they cost nothing
  semantically. (``min_prefill_rows`` overrides the auto gang threshold.)

Requests are admitted FCFS as slots free up and retired per token on
EOS/max-token stops — the cache never reshapes, so the engine compiles two
programs per sampling mode actually used (greedy temp-0 variants skip the
RNG; a workload mixing temperatures compiles both), plus a per-slot
encoder program for enc-dec archs. Re-admission compiles nothing: slot
reuse is a pure data change, asserted by `trace_counts` in tests. With a
mesh, params and cache are placed by `param_specs`/`cache_specs`, host
arrays by `serve_arg_specs`, and every program lowers sharded (batch/slot
dim over ``data``, heads over ``model``).
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist.sharding import cache_specs, named, param_specs, serve_arg_specs  # noqa: F401
from repro.dist.steps import make_prefill_step, make_serve_step
from repro.launch.mesh import make_mesh
from repro.models import transformer as T
from repro.models.config import ArchConfig
from repro.serve.metrics import EngineMetrics, RequestMetrics
from repro.serve.prefill import plan_chunk
from repro.serve.scheduler import FCFSScheduler, Phase, Request, RequestState, stop_reason

__all__ = ["EngineConfig", "ServeEngine"]


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    max_concurrency: int = 8       # cache slots = max in-flight requests
    max_len: int = 128             # per-slot cache capacity (prompt + gen)
    chunk: int = 16                # prefill tokens per slot per step
    min_prefill_rows: int = 0      # gang-prefill threshold: run the chunked
                                   # program only when this many slots are
                                   # prefilling; fewer rows piggyback on
                                   # decode steps. 0 = auto: gang when
                                   # prefilling rows >= decoding rows (fill
                                   # fast at low occupancy, never stall a
                                   # busy decode batch for a lone prompt)
    dtype: object = jnp.float32
    seed: int = 0
    donate_cache: bool = False     # donate the cache to each step program —
                                   # enable on accelerators (halves cache
                                   # HBM); measured ~1ms/call SLOWER on the
                                   # CPU backend, so off by default


def _sample_tokens(logits: jax.Array, key: jax.Array, temps: jax.Array) -> jax.Array:
    """Per-row greedy/temperature sampling. logits (B, V) f32; temps (B,)
    with temp <= 0 meaning greedy (argmax — identical to the sequential
    decode reference, so temp-0 engine outputs are bit-identical)."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    keys = jax.random.split(key, logits.shape[0])
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled).astype(jnp.int32)
    return jnp.where(temps > 0, sampled, greedy)


def _zero_fresh_state(cache: dict, fresh: jax.Array) -> dict:
    """Zero the recurrent-state rows (conv/ssm) of freshly admitted slots.

    Attention slots need no reset — their ring mask hides everything past
    the slot's position — but mamba state is position-free and would leak
    the previous occupant's state into the new request."""

    def one(kp, leaf):
        name = str(getattr(kp[-1], "key", kp[-1])) if kp else ""
        if name in ("conv", "ssm"):
            m = fresh.reshape((1, fresh.shape[0]) + (1,) * (leaf.ndim - 2))
            return jnp.where(m, jnp.zeros((), leaf.dtype), leaf)
        return leaf

    return jax.tree_util.tree_map_with_path(one, cache)


class ServeEngine:
    """Continuous-batching engine; see module docstring.

    Typical use::

        eng = ServeEngine(cfg, params, EngineConfig(max_concurrency=8))
        for r in requests:
            eng.submit(r)           # Request(rid, prompt, max_tokens, ...)
        results = eng.run()         # list[RequestState] sorted by rid
    """

    def __init__(self, cfg: ArchConfig, params, engine: EngineConfig | None = None,
                 mesh=None, obs=None):
        self.cfg = cfg
        self.engine = engine or EngineConfig()
        # optional shared repro.obs.Recorder: engine counters/latency
        # histograms land there as serve/* series plus a per-step duration
        # per device program. Host-side only, between device calls — token
        # streams are bit-identical with obs on or off.
        self.obs = obs
        b, s = self.engine.max_concurrency, self.engine.max_len
        ring = min(s, cfg.sliding_window) if cfg.sliding_window > 0 else s
        self.ring_size = ring
        self.chunk = min(self.engine.chunk, ring)
        self.min_prefill_rows = self.engine.min_prefill_rows  # 0 = auto
        self.mesh = mesh if mesh is not None else make_mesh((1, 1), ("data", "model"))

        serve_fn, p_specs = make_serve_step(cfg, self.mesh, slots=True)
        prefill_fn, _ = make_prefill_step(cfg, self.mesh)
        self.param_spec_tree = p_specs
        self.params = jax.device_put(params, named(p_specs, self.mesh))
        cache = T.init_cache(cfg, b, s, self.engine.dtype,
                             enc_len=cfg.frontend_tokens if cfg.enc_dec else 0)
        self.cache = jax.device_put(cache, named(cache_specs(cache, self.mesh), self.mesh))

        # Per-step host arrays ride the data axis with the cache's slot dim
        # (serve_arg_specs); placement only matters on real multi-device
        # meshes, so the single-device path skips the extra device_puts.
        self._place_args = self.mesh.size > 1
        if self._place_args:
            abstract = {
                "token": jax.ShapeDtypeStruct((b, 1), jnp.int32),
                "tokens": jax.ShapeDtypeStruct((b, self.chunk), jnp.int32),
                "i32": jax.ShapeDtypeStruct((b,), jnp.int32),
                "bool": jax.ShapeDtypeStruct((b,), jnp.bool_),
                "f32": jax.ShapeDtypeStruct((b,), jnp.float32),
            }
            self._arg_sharding = named(serve_arg_specs(abstract, self.mesh), self.mesh)

        self.trace_counts = {"prefill": 0, "decode": 0}
        if cfg.enc_dec:
            self.trace_counts["encode"] = 0

            def encode_body(params, enc_out, embeds, slot):
                self.trace_counts["encode"] += 1
                one = T._run_encoder(cfg, params, embeds, remat=False)
                return jax.lax.dynamic_update_slice(
                    enc_out, one.astype(enc_out.dtype), (slot, 0, 0))

            self._encode = jax.jit(encode_body)

        def prefill_logits(params, cache, tokens, positions, n_valid):
            self.trace_counts["prefill"] += 1  # python side: counts traces
            fresh = (positions == 0) & (n_valid > 0)
            cache = _zero_fresh_state(cache, fresh)
            logits, cache = prefill_fn(params, cache, tokens, positions, n_valid)
            idx = jnp.clip(n_valid - 1, 0, tokens.shape[1] - 1)
            last = jnp.take_along_axis(logits, idx[:, None, None], axis=1)[:, 0]
            return cache, last.astype(jnp.float32)

        def decode_logits(params, cache, token, positions, active):
            self.trace_counts["decode"] += 1
            # an active row at position 0 is a piggybacked first prompt
            # token on a freshly admitted slot — its recurrent state must
            # be zeroed here, it never passes through the prefill program
            fresh = active & (positions == 0)
            cache = _zero_fresh_state(cache, fresh)
            logits, cache = serve_fn(params, cache, token, positions, active)
            return cache, logits[:, 0].astype(jnp.float32)

        # Greedy (temperature-0) variants skip the RNG entirely — no key
        # split, no gumbel draw, two fewer host->device transfers per step.
        def prefill_body(params, cache, tokens, positions, n_valid, key, temps):
            cache, last = prefill_logits(params, cache, tokens, positions, n_valid)
            return cache, _sample_tokens(last, key, temps)

        def prefill_greedy(params, cache, tokens, positions, n_valid):
            cache, last = prefill_logits(params, cache, tokens, positions, n_valid)
            return cache, jnp.argmax(last, axis=-1).astype(jnp.int32)

        def decode_body(params, cache, token, positions, active, key, temps):
            cache, last = decode_logits(params, cache, token, positions, active)
            return cache, _sample_tokens(last, key, temps)

        def decode_greedy(params, cache, token, positions, active):
            cache, last = decode_logits(params, cache, token, positions, active)
            return cache, jnp.argmax(last, axis=-1).astype(jnp.int32)

        donate = (1,) if self.engine.donate_cache else ()
        self._prefill_sampled = jax.jit(prefill_body, donate_argnums=donate)
        self._prefill_greedy = jax.jit(prefill_greedy, donate_argnums=donate)
        self._decode_sampled = jax.jit(decode_body, donate_argnums=donate)
        self._decode_greedy = jax.jit(decode_greedy, donate_argnums=donate)

        self.reset()

    def reset(self) -> None:
        """Clear all request state (queue, slots, metrics, RNG) while
        keeping the compiled programs and the allocated cache — stale cache
        contents are invisible behind the ring masks, and recurrent state
        is zeroed on admission. Lets a long-lived engine serve independent
        workloads without paying compilation twice."""
        b = self.engine.max_concurrency
        self.scheduler = FCFSScheduler()
        self.metrics = EngineMetrics(recorder=self.obs)
        self._slots: list[RequestState | None] = [None] * b
        self.positions = np.zeros((b,), np.int32)
        self._last_tok = np.zeros((b,), np.int32)
        self._temps = np.zeros((b,), np.float32)
        self._key = jax.random.PRNGKey(self.engine.seed)
        self._step_count = 0
        self._work_budget = 0
        # per-request causal span chains (repro.obs.trace): last span id and
        # a per-request sequence counter for unique step-span ids
        self._tracing = (self.obs is not None
                         and getattr(self.obs, "trace_enabled", False))
        self._trace_prev: dict[int, str] = {}
        self._trace_seq: dict[int, int] = {}

    # ------------------------------------------------------------- lifecycle
    def _arg(self, x, kind: str):
        """Place a per-step host array per serve_arg_specs (multi-device)."""
        return jax.device_put(x, self._arg_sharding[kind]) if self._place_args else x

    def _admit_enc(self, st: RequestState) -> None:
        """enc-dec: run the encoder for the admitted request and write its
        output into the slot's row of the shared enc_out cache."""
        if not self.cfg.enc_dec:
            return
        emb = np.asarray(st.request.embeds, np.float32)[None]  # (1, F, d)
        with self.mesh:
            enc_out = self._encode(self.params, self.cache["enc_out"], emb,
                                   np.int32(st.slot))
        cache = dict(self.cache)
        cache["enc_out"] = enc_out
        self.cache = cache

    def submit(self, req: Request) -> None:
        if req.rid in self.metrics.requests:
            raise ValueError(f"duplicate request id {req.rid}")
        total = len(req.prompt) + req.max_tokens
        if self.cfg.has_attention and self.cfg.sliding_window == 0 \
                and total > self.engine.max_len:
            raise ValueError(
                f"request {req.rid}: prompt+max_tokens {total} exceeds "
                f"max_len {self.engine.max_len} (full-attention cache)")
        if self.cfg.enc_dec:
            want = (self.cfg.frontend_tokens, self.cfg.d_model)
            got = None if req.embeds is None else tuple(np.shape(req.embeds))
            if got != want:
                raise ValueError(
                    f"request {req.rid}: enc-dec arch needs embeds of shape "
                    f"{want}, got {got}")
        self.scheduler.submit(req)
        self.metrics.requests[req.rid] = RequestMetrics(
            rid=req.rid, prompt_len=len(req.prompt), arrival_step=req.arrival_step)
        # worst case: the whole prompt streams via piggyback decode steps
        self._work_budget += req.arrival_step + req.max_tokens + len(req.prompt) + 2

    def in_flight(self) -> int:
        return sum(st is not None for st in self._slots)

    def pending(self) -> bool:
        return self.in_flight() > 0 or len(self.scheduler) > 0

    def _next_key(self) -> jax.Array:
        self._key, sub = jax.random.split(self._key)
        return sub

    def _emit_token(self, st: RequestState, tok: int,
                    finished: list[RequestState], first: bool = False) -> None:
        st.generated.append(tok)
        self._last_tok[st.slot] = tok
        now = self.metrics.now()
        rm = self.metrics.requests[st.request.rid]
        if first:
            rm.first_token_wall = now
            rm.eligible_wall = self.scheduler.eligible_wall.get(st.request.rid, now)
        rm.n_generated = len(st.generated)
        self.metrics.generated_tokens += 1
        reason = stop_reason(st.request, st.generated)
        if reason:
            st.stop = reason
            st.phase = Phase.FINISHED
            rm.finish_wall = now
            rm.finish_step = self._step_count
            self._slots[st.slot] = None  # slot is immediately reusable
            self._temps[st.slot] = 0.0   # don't hold the sampled path open
            if self.obs is not None:
                self.metrics.observe_request(rm)
            finished.append(st)

    # ------------------------------------------------------------------ step
    def step(self) -> list[RequestState]:
        """One engine iteration: admit, then run ONE device program — a
        gang prefill chunk when an admission wave justifies it, else a
        decode step that lone prefilling slots piggyback on (one prompt
        token at their own position). Returns the requests that finished
        during this step."""
        now_step = self._step_count
        self._step_count += 1
        self.metrics.engine_steps += 1
        t_step0 = self.metrics.now() if self.obs is not None else 0.0
        finished: list[RequestState] = []

        # admit() also stamps arrival eligibility on waiting requests, so it
        # runs even when no slot is free — queueing delay counts in TTFT
        free = [i for i, st in enumerate(self._slots) if st is None]
        for st in self.scheduler.admit(free, now_step, self.metrics.now()):
            self._slots[st.slot] = st
            self.positions[st.slot] = 0
            self._temps[st.slot] = st.request.temperature
            self.metrics.requests[st.request.rid].admit_step = now_step
            self._admit_enc(st)
            if self._tracing:
                rid = st.request.rid
                now = self.metrics.now()
                sid = f"r{rid}.admit"
                self.obs.trace_span(
                    "admit", trace=f"r{rid}", span=sid,
                    t0=self.scheduler.eligible_wall.get(rid, now), t1=now,
                    rid=rid, slot=st.slot)
                self._trace_prev[rid] = sid
                self._trace_seq[rid] = 0

        prefilling = [st for st in self._slots if st is not None
                      and st.phase is Phase.PREFILL]
        decoding = [st for st in self._slots if st is not None
                    and st.phase is Phase.DECODE]

        sampled = bool(np.any(self._temps > 0))
        gang_at = self.min_prefill_rows or max(1, len(decoding))
        if prefilling and (len(prefilling) >= gang_at or not decoding):
            tokens, n_valid = plan_chunk(prefilling, len(self._slots), self.chunk)
            # Trace/run inside the mesh context so the model's sharding
            # constraints (split guards, batch-parallel attention) bind.
            tokens = self._arg(tokens, "tokens")
            pos = self._arg(self.positions.copy(), "i32")
            n_valid_dev = self._arg(n_valid, "i32")
            with self.mesh:
                if sampled:
                    self.cache, tok = self._prefill_sampled(
                        self.params, self.cache, tokens, pos, n_valid_dev,
                        self._next_key(), self._arg(self._temps.copy(), "f32"))
                else:
                    self.cache, tok = self._prefill_greedy(
                        self.params, self.cache, tokens, pos, n_valid_dev)
            tok = np.asarray(tok)
            for st in prefilling:
                m = int(n_valid[st.slot])
                st.prompt_done += m
                self.positions[st.slot] += m
                self.metrics.prompt_tokens += m
                if st.prompt_remaining == 0:
                    st.phase = Phase.DECODE
                    self._emit_token(st, int(tok[st.slot]), finished, first=True)
            self.metrics.prefill_chunks += 1
            self.metrics.touch()
            if self._tracing:
                t1 = self.metrics.now()
                for st in prefilling:
                    self._trace_step_span("prefill_chunk", st, t_step0, t1,
                                          tokens=int(n_valid[st.slot]))
            self._note_step("prefill", t_step0)
            return finished

        if decoding or prefilling:
            active = np.zeros((len(self._slots),), bool)
            token = self._last_tok.copy()
            for st in decoding:
                active[st.slot] = True
            for st in prefilling:  # piggyback: next prompt token, 1/step
                active[st.slot] = True
                token[st.slot] = st.request.prompt[st.prompt_done]
            token_dev = self._arg(token[:, None], "token")
            pos = self._arg(self.positions.copy(), "i32")
            active_dev = self._arg(active, "bool")
            with self.mesh:
                if sampled:
                    self.cache, tok = self._decode_sampled(
                        self.params, self.cache, token_dev, pos, active_dev,
                        self._next_key(), self._arg(self._temps.copy(), "f32"))
                else:
                    self.cache, tok = self._decode_greedy(
                        self.params, self.cache, token_dev, pos, active_dev)
            tok = np.asarray(tok)
            for st in prefilling:
                st.prompt_done += 1
                self.positions[st.slot] += 1
                self.metrics.prompt_tokens += 1
                self.metrics.piggyback_tokens += 1
                if st.prompt_remaining == 0:
                    # this step consumed the last prompt token, so its
                    # logits already yield the first generated token
                    st.phase = Phase.DECODE
                    self._emit_token(st, int(tok[st.slot]), finished, first=True)
            for st in decoding:
                self.positions[st.slot] += 1
                self._emit_token(st, int(tok[st.slot]), finished)
            self.metrics.decode_steps += 1
            self.metrics.touch()
            if self._tracing:
                t1 = self.metrics.now()
                for st in prefilling:   # piggybacked prompt token
                    self._trace_step_span("prefill_chunk", st, t_step0, t1,
                                          tokens=1, piggyback=1)
                for st in decoding:
                    self._trace_step_span("decode", st, t_step0, t1)
            self._note_step("decode", t_step0)
        else:
            self.metrics.idle_steps += 1  # waiting on a future arrival_step
            self._note_step("idle", t_step0)
        return finished

    def _trace_step_span(self, kind: str, st: RequestState, t0: float,
                         t1: float, **attrs) -> None:
        """One node of a request's causal chain: admit -> prefill_chunk* ->
        decode* — each step span parented on the request's previous span."""
        rid = st.request.rid
        seq = self._trace_seq.get(rid, 0)
        self._trace_seq[rid] = seq + 1
        sid = f"r{rid}.{'p' if kind == 'prefill_chunk' else 'd'}{seq}"
        self.obs.trace_span(kind, trace=f"r{rid}", span=sid,
                            parent=self._trace_prev.get(rid),
                            t0=t0, t1=t1, rid=rid, slot=st.slot, **attrs)
        self._trace_prev[rid] = sid

    def _note_step(self, kind: str, t0: float) -> None:
        """Flush one step's telemetry at the step boundary (never inside the
        jitted programs)."""
        if self.obs is None:
            return
        self.obs.duration("serve/step", self.metrics.now() - t0, kind=kind)
        self.obs.flush()

    # ------------------------------------------------------------------- run
    def run(self, requests=None) -> list[RequestState]:
        """Submit `requests` (optional) and step until everything finishes.
        Returns finished RequestStates sorted by request id."""
        for r in requests or ():
            self.submit(r)
        self.metrics.start()
        done: list[RequestState] = []
        guard = 2 * self._work_budget + 64
        while self.pending():
            done.extend(self.step())
            guard -= 1
            if guard <= 0:
                raise RuntimeError(
                    f"engine stalled: {self.in_flight()} in flight, "
                    f"{len(self.scheduler)} waiting after {self._step_count} steps")
        return sorted(done, key=lambda st: st.request.rid)
