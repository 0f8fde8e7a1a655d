"""Unified telemetry (`repro.obs`): recorder/stream/report units plus the
two invariants the layer is built on —

* **off the hot path**: attaching a recorder changes NOTHING about a run
  (bit-identical device params / virtual time / token streams, same
  trace_count) for the round engine, both simulator engines and serving;
* **deterministic sim streams**: simulator events are priced in virtual
  seconds and carry no host wall times, so the same scenario + seed yields
  byte-identical event/summary lines.

Also the retrace-audit regression: the round engine's retrace warning is
re-armable (a second unstable shape later in a run warns again), with
``programs_run``/``retrace_count`` exposed and exported as a monotone
``engine/retraces`` counter.
"""
import json
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DFedRW, DFedRWConfig, QuantConfig, make_topology
from repro.core.heterogeneity import partition_similarity
from repro.data import FederatedDataset, synthetic_image_classification
from repro.models import make_fnn
from repro.obs import (
    HIST_RESERVOIR,
    OBS_COMPAT_VERSIONS,
    OBS_SCHEMA,
    OBS_SCHEMA_VERSION,
    ObsStream,
    PausableWallClock,
    PROVENANCE_KEYS,
    Recorder,
    VirtualClock,
    WallClock,
    config_hash,
    jax_profile,
    make_obs_header,
    provenance,
    render_prometheus,
    render_report,
)
from repro.sim import build_scenario


# ---------------------------------------------------------------- recorder
def test_counter_flush_deltas_and_totals():
    rec = Recorder(clock=VirtualClock(lambda: 1.0))
    rec.counter("a", 3)
    rec.counter("a", 2)
    rec.flush()
    rec.counter("a", 5)
    rec.flush()
    rec.flush()  # nothing changed: no event
    assert rec.value("a") == 10.0
    flushes = [e for e in rec.events if e["kind"] == "flush"]
    assert [f["counters"]["a"] for f in flushes] == [5.0, 5.0]
    assert sum(f["counters"]["a"] for f in flushes) == rec.value("a")


def test_label_keys_sorted_and_stable():
    rec = Recorder()
    rec.counter("engine/comm_bits", 1, bits=8, phase="x")
    rec.counter("engine/comm_bits", 2, phase="x", bits=8)  # kwarg order swap
    assert rec.value("engine/comm_bits", bits=8, phase="x") == 3.0
    assert 'engine/comm_bits{bits="8",phase="x"}' in rec._counters


def test_gauge_snapshot_on_flush():
    rec = Recorder()
    rec.gauge("sim/bits", 8)
    rec.flush()
    rec.flush()  # gauge unchanged: no second event
    rec.gauge("sim/bits", 4)
    rec.flush()
    gauges = [e["gauges"]["sim/bits"] for e in rec.events if "gauges" in e]
    assert gauges == [8.0, 4.0]


def test_histogram_moments_and_reservoir_cap():
    rec = Recorder()
    rec.histogram("h", 3.0)                       # scalar
    rec.histogram("h", np.arange(HIST_RESERVOIR + 100))  # array form
    s = rec.summary()["hists"]["h"]
    assert s["count"] == HIST_RESERVOIR + 101
    assert s["min"] == 0.0 and s["max"] == HIST_RESERVOIR + 99
    assert s["p50"] <= s["p90"] <= s["p99"] <= s["max"]
    # strided thinning: bounded, deterministic, and covering the whole run
    h = rec._hists["h"]
    assert len(h.samples) < HIST_RESERVOIR
    assert h.stride > 1
    assert max(h.samples) >= HIST_RESERVOIR  # late observations survive


def test_histogram_thinning_unbiased_percentiles():
    # Regression for the keep-first reservoir: after the cap, percentiles
    # only reflected the run's start (p50 of 0..9999 reported ~2048).
    rec = Recorder()
    rec.histogram("h", np.arange(10_000))
    s = rec.summary()["hists"]["h"]
    assert abs(s["p50"] - 5_000) < 300
    assert abs(s["p90"] - 9_000) < 300
    assert abs(s["p99"] - 9_900) < 300


def test_histogram_thinning_deterministic():
    # Same feed -> same kept samples (no RNG), split points irrelevant.
    a, b = Recorder(), Recorder()
    vals = np.arange(12_345, dtype=float)
    a.histogram("h", vals)
    for chunk in np.array_split(vals, 17):
        b.histogram("h", chunk)
    assert a._hists["h"].samples == b._hists["h"].samples
    assert a._hists["h"].stride == b._hists["h"].stride


def test_span_duration_and_record_span():
    t = {"now": 0.0}
    rec = Recorder(clock=VirtualClock(lambda: t["now"]))
    with rec.span("w"):
        t["now"] = 2.5
    rec.record_span("w", 10.0, 11.0)
    rec.duration("d", 0.25, t=11.0)
    spans = rec.summary()["spans"]
    assert spans["w"] == {"count": 2, "total_s": 3.5}
    assert spans["d"] == {"count": 1, "total_s": 0.25}
    kinds = [e["kind"] for e in rec.events]
    assert kinds == ["span", "span", "dur"]
    assert rec.events[-1] == {"kind": "dur", "name": "d", "t": 11.0, "dur": 0.25}


# ------------------------------------------------------------------ clocks
def test_clock_kinds_and_semantics():
    assert WallClock().kind == "wall"
    assert PausableWallClock().kind == "wall-active"
    assert VirtualClock().kind == "virtual"

    pw = PausableWallClock()
    t0 = pw.now()
    pw.note_pause(100.0)
    assert pw.now() < t0 - 99.0  # paused time is credited away

    vc = VirtualClock()
    assert not vc.bound and vc.now() == 0.0
    vc.bind(lambda: 42.0)
    assert vc.bound and vc.now() == 42.0


def test_unbound_virtual_clock_warns_once_and_flags_header():
    """Recording spans against an unbound VirtualClock (every timestamp
    silently 0.0) warns exactly once and marks the stream header."""
    rec = Recorder(clock=VirtualClock())
    with pytest.warns(UserWarning, match="unbound VirtualClock"):
        rec.record_span("sim/window", 0.0, 1.0)
    with warnings.catch_warnings():
        warnings.simplefilter("error")          # one-shot: no second warning
        rec.record_span("sim/window", 1.0, 2.0)
        with rec.span("x"):
            pass
    assert rec.to_stream().header["clock_unbound"] is True

    bound = Recorder(clock=VirtualClock(lambda: 5.0))
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        bound.record_span("sim/window", 0.0, 1.0)
    assert "clock_unbound" not in bound.to_stream().header


def test_jax_profile_noop_paths():
    with jax_profile(None):       # falsy logdir: plain no-op
        pass
    with jax_profile(""):
        pass


def test_jax_profile_writes_trace_and_raises(tmp_path):
    with jax_profile(str(tmp_path / "a")):
        jnp.ones(4).block_until_ready()
    assert list((tmp_path / "a").rglob("*.xplane.pb"))
    with jax_profile(str(tmp_path / "b")):
        # a second session cannot start while one runs: the failure surfaces
        with pytest.raises(RuntimeError):
            with jax_profile(str(tmp_path / "c")):
                pass


# ------------------------------------------------------------------ stream
def test_stream_round_trip(tmp_path):
    rec = Recorder(clock=VirtualClock(lambda: 2.0))
    rec.counter("engine/rounds", 3)
    rec.gauge("sim/bits", 8)
    stream = rec.to_stream(provenance=provenance(), workload="sim",
                           scenario="x")
    path = tmp_path / "obs.jsonl"
    stream.save(str(path))
    back = ObsStream.load(str(path))
    assert back.header["schema"] == OBS_SCHEMA
    assert back.header["version"] == OBS_SCHEMA_VERSION
    assert back.header["clock"] == "virtual"
    assert back.header["workload"] == "sim" and back.header["scenario"] == "x"
    assert all(k in back.header["provenance"] for k in PROVENANCE_KEYS)
    assert back.summary["counters"]["engine/rounds"] == 3.0
    assert back.events == stream.events
    assert back.to_lines() == stream.to_lines()


def test_stream_rejects_foreign_schema_and_version():
    good = make_obs_header(clock="wall")
    with pytest.raises(ValueError, match="not a repro.obs"):
        ObsStream.from_lines([json.dumps({**good, "schema": "repro.trace"})])
    bad_version = max(OBS_COMPAT_VERSIONS) + 1
    with pytest.raises(ValueError, match="version"):
        ObsStream.from_lines([json.dumps({**good, "version": bad_version})])


def test_prometheus_format():
    rec = Recorder()
    rec.counter("engine/comm_bits", 640, bits=8)
    rec.gauge("sim/bits", 8)
    with rec.span("sim/window"):
        pass
    text = rec.to_prometheus()
    # suffix goes BEFORE the label braces (valid exposition format)
    assert 'repro_engine_comm_bits_total{bits="8"} 640' in text
    assert "repro_sim_bits 8" in text
    assert "repro_sim_window_seconds_count 1" in text
    assert "repro_sim_window_seconds_sum" in text
    # the stream-side renderer agrees on counters/gauges
    text2 = render_prometheus(rec.to_stream())
    assert 'repro_engine_comm_bits_total{bits="8"} 640' in text2
    assert "repro_sim_bits 8" in text2


def test_prometheus_histogram_quantiles_and_extremes():
    rec = Recorder()
    rec.histogram("serve/ttft_s", [1.0, 2.0, 3.0, 4.0])
    rec.histogram("sim/steps", [10, 20], phase="walk")   # labeled series
    for text in (rec.to_prometheus(), render_prometheus(rec.to_stream())):
        assert 'repro_serve_ttft_s{quantile="0.5"}' in text
        assert 'repro_serve_ttft_s{quantile="0.9"}' in text
        assert 'repro_serve_ttft_s{quantile="0.99"} 4' in text
        assert "repro_serve_ttft_s_min 1" in text
        assert "repro_serve_ttft_s_max 4" in text
        # quantile label splices INTO an existing label set
        assert 'repro_sim_steps{phase="walk",quantile="0.5"}' in text
        assert 'repro_sim_steps_min{phase="walk"} 10' in text


# -------------------------------------------------------------- provenance
def test_provenance_keys_and_config_hash():
    p = provenance(config={"b": 2, "a": 1})
    for k in PROVENANCE_KEYS:
        assert k in p, k
    assert p["config_hash"] == config_hash({"a": 1, "b": 2})  # order-free
    assert config_hash({"a": 1}) != config_hash({"a": 2})
    assert "config_hash" not in provenance()


# ------------------------------------------- round engine + retrace re-arm
@pytest.fixture(scope="module")
def engine_setup():
    x, y = synthetic_image_classification(n_samples=1000, seed=0, noise=1.0)
    part = partition_similarity(y, 8, 50, np.random.default_rng(0))
    data = FederatedDataset.from_partition(x, y, part)
    topo = make_topology("complete", 8)
    model = make_fnn((32,))
    return data, topo, model


def test_retrace_warning_rearms_and_exports(engine_setup):
    """Regression: the retrace warning used to be a fire-once latch — a
    SECOND unstable plan shape later in the run was silently absorbed. Now
    every new retrace warns again, and the monotone facts are exposed as
    ``programs_run``/``retrace_count`` + the ``engine/retraces`` series."""
    data, topo, model = engine_setup
    eng = DFedRW(model, data, topo,
                 DFedRWConfig(m_chains=4, k_walk=3, batch_size=16))
    rec = Recorder()
    eng.attach_obs(rec)
    key = jax.random.PRNGKey(0)
    state = eng.init_state(key)

    key, sub = jax.random.split(key)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # the first trace is not a retrace
        state, _ = eng.run_round(state, sub)
    assert eng.programs_run == (32,)
    assert eng.retrace_count == 0

    def odd_round(state, m):
        plan, bidx = eng.plan_walks(state, m=m)
        agg = eng.plan_aggregation(plan)
        return eng.execute_round(state, plan, bidx, agg,
                                 jax.random.PRNGKey(m))

    with pytest.warns(UserWarning, match="retraced"):
        state, _ = odd_round(state, 3)     # unstable shape #1
    assert eng.retrace_count == 1

    key, sub = jax.random.split(key)
    with warnings.catch_warnings():
        warnings.simplefilter("error")     # back on the cached shape: silent
        state, _ = eng.run_round(state, sub)

    with pytest.warns(UserWarning, match="2 retrace"):
        state, _ = odd_round(state, 2)     # unstable shape #2 warns AGAIN
    assert eng.retrace_count == 2
    assert eng.programs_run == (32,)       # still one wire width
    assert rec.value("engine/retraces") == 2.0
    assert rec.value("engine/rounds") == 4.0


def test_engine_obs_series(engine_setup):
    data, topo, model = engine_setup
    eng = DFedRW(model, data, topo,
                 DFedRWConfig(m_chains=4, k_walk=3, batch_size=16,
                              quant=QuantConfig(bits=8)))
    rec = Recorder()
    eng.attach_obs(rec)
    key = jax.random.PRNGKey(1)
    state = eng.init_state(key)
    for _ in range(2):
        key, sub = jax.random.split(key)
        state, m = eng.run_round(state, sub)
    assert rec.value("engine/rounds") == 2.0
    assert rec.value("engine/programs", bits=8) == 2.0
    assert rec.value("engine/comm_bits", bits=8) == state.comm_bits_total
    assert rec.value("engine/comm_bits_busiest") == state.comm_bits_busiest
    spans = rec.summary()["spans"]
    assert spans["engine/plan"]["count"] == 2
    assert spans["engine/execute_round"]["count"] == 2


@pytest.mark.parametrize("bits", [8, 32])
def test_engine_merge_counters(bits):
    """``engine/merge_rows`` and ``engine/merge_groups`` count the rows the
    round's row merge writes and the 8-row groups holding them, from the
    plan: one pass at bits < 32, two at 32 (walk winners, aggregators)."""
    n = 20
    x, y = synthetic_image_classification(n_samples=400, seed=0, noise=1.0)
    data = FederatedDataset.from_partition(
        x, y, partition_similarity(y, n, 50, np.random.default_rng(0)))
    eng = DFedRW(make_fnn((16,)), data, make_topology("complete", n),
                 DFedRWConfig(m_chains=4, k_walk=3, batch_size=8,
                              quant=QuantConfig(bits=bits)))
    rec = Recorder()
    eng.attach_obs(rec)
    key = jax.random.PRNGKey(2)
    state = eng.init_state(key)
    rows = groups = 0
    for r in range(3):
        plan, bidx = eng.plan_walks(state)
        agg = eng.plan_aggregation(plan)
        walked = {int(dv) for dv, on in zip(plan.devices.flat, plan.mask.flat) if on}
        mixed = {int(a) for a in agg[0] if a < n}
        passes = [walked | mixed] if bits < 32 else [walked, mixed]
        rows += sum(len(p) for p in passes)
        groups += sum(len({i // 8 for i in p}) for p in passes)
        state, _ = eng.execute_round(state, plan, bidx, agg, jax.random.PRNGKey(r))
    assert rec.value("engine/merge_rows") == rows > 0
    assert rec.value("engine/merge_groups") == groups > 3


# ------------------------------------------------- simulator: bit-exactness
SIM_CASES = [("straggler_tail", "heap", 8), ("million_walks", "fleet", 20)]


def _sim_run(scenario, engine, n, rec=None, rounds=3):
    setup = build_scenario(scenario, n=n, seed=0, rounds=rounds)
    runner = setup.runner(engine=engine)
    if rec is not None:
        runner.attach_obs(rec)
    result = runner.run(rounds, jax.random.PRNGKey(0),
                        setup.x_test, setup.y_test, eval_every=rounds)
    return runner, result


@pytest.mark.parametrize("scenario,engine,n", SIM_CASES)
def test_sim_obs_on_vs_off_bit_exact(scenario, engine, n):
    """Attaching a recorder changes nothing: params, virtual time and the
    compiled-program table are identical — on the heap AND fleet engines."""
    r_off, res_off = _sim_run(scenario, engine, n)
    rec = Recorder(clock=VirtualClock())
    r_on, res_on = _sim_run(scenario, engine, n, rec=rec)
    np.testing.assert_array_equal(np.asarray(res_off.state.device_params),
                                  np.asarray(res_on.state.device_params))
    assert r_off.t == r_on.t
    assert r_off.engine.trace_count == r_on.engine.trace_count
    assert rec.value("sim/windows") == 3.0
    assert rec.events, "instrumented run recorded nothing"


@pytest.mark.parametrize("scenario,engine,n", SIM_CASES)
def test_sim_obs_stream_deterministic(scenario, engine, n):
    """Same scenario + seed -> byte-identical stream: events carry only
    virtual-time/count data (provenance/timestamps live on the header)."""
    lines = []
    for _ in range(2):
        rec = Recorder(clock=VirtualClock())
        _sim_run(scenario, engine, n, rec=rec)
        lines.append(rec.to_stream(workload="sim", scenario=scenario).to_lines())
    assert lines[0] == lines[1]


def test_sim_window_series(tmp_path):
    rec = Recorder(clock=VirtualClock())
    runner, _ = _sim_run("overlap_async", "heap", 8, rec=rec)
    c = {k: v for k, v in rec.summary()["counters"].items()}
    assert c["sim/windows"] == 3.0
    assert c["sim/events"] > 0
    spans = rec.summary()["spans"]
    for name in ("sim/window", "sim/walk", "sim/aggregate"):
        assert spans[name]["count"] == 3
    # window spans are priced in virtual seconds up to the runner's clock
    assert spans["sim/window"]["total_s"] <= runner.t + 1e-9
    # the stream renders end to end
    rec.save(str(tmp_path / "obs.jsonl"), workload="sim")
    report = render_report(ObsStream.load(str(tmp_path / "obs.jsonl")))
    assert "time in phase" in report and "sim/window" in report


# ----------------------------------------------------------------- serving
def test_serve_obs_on_vs_off_token_parity():
    from repro.models import transformer as T
    from repro.models.config import ArchConfig
    from repro.serve import EngineConfig, Request, ServeEngine

    cfg = ArchConfig(name="d", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=64, qkv_bias=True)
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(3)
    reqs = [Request(rid=i,
                    prompt=rng.integers(0, cfg.vocab,
                                        size=(int(rng.integers(2, 12)),)),
                    max_tokens=int(rng.integers(2, 8)), eos_id=-1)
            for i in range(6)]
    econf = EngineConfig(max_concurrency=2, max_len=32, chunk=8)

    off = ServeEngine(cfg, params, econf).run(reqs)
    rec = Recorder(clock=PausableWallClock())
    eng = ServeEngine(cfg, params, econf, obs=rec)
    on = eng.run(reqs)
    assert [st.generated for st in on] == [st.generated for st in off]
    assert rec.value("serve/requests_finished") == len(reqs)
    hists = rec.summary()["hists"]
    assert hists["serve/ttft_s"]["count"] == len(reqs)
    assert hists["serve/tpot_s"]["count"] == len(reqs)
    steps = rec.summary()["spans"]
    total_steps = sum(v["count"] for k, v in steps.items()
                      if k.startswith("serve/step"))
    assert total_steps == eng.metrics.engine_steps


# ------------------------------------------------------------------ report
def _synthetic_stream(retraces=0):
    rec = Recorder(clock=VirtualClock(lambda: 10.0))
    rec.record_span("sim/window", 0.0, 10.0)
    rec.counter("engine/comm_bits", 8e6, bits=8)
    rec.counter("engine/comm_bits", 2e6, bits=4)
    rec.counter("engine/programs", 3, bits=8)
    rec.counter("engine/programs", 1, bits=4)
    if retraces:
        rec.counter("engine/retraces", retraces)
    rec.histogram("sim/window_steps", [1, 2, 3, 8])
    return rec.to_stream(workload="test")


def test_report_sections_and_retrace_warning():
    quiet = render_report(_synthetic_stream())
    assert "communication by wire width" in quiet
    assert "no retraces" in quiet and "WARNING" not in quiet
    assert "sim/window_steps" in quiet

    noisy = render_report(_synthetic_stream(retraces=2))
    assert "WARNING: 2 retrace(s)" in noisy


def test_report_rebuilds_without_summary():
    stream = _synthetic_stream()
    cut = ObsStream(header=stream.header, events=stream.events, summary=None)
    report = render_report(cut)
    # counters/spans are rebuilt from the raw lines (hists need the summary)
    assert "communication by wire width" in report
    assert "sim/window" in report
