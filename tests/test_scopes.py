"""One name per layer: the device scopes of the flat round program and of the
pod fed step reach the compiled program's op metadata, wall-clock recorder
spans are profiler annotations, and the round engine's host spans nest in
their documented order."""
import json
import os
import re
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DFedRW, DFedRWConfig, QuantConfig, make_topology
from repro.core.heterogeneity import partition_similarity
from repro.data import FederatedDataset, synthetic_image_classification
from repro.models import make_fnn
from repro.obs import (ENGINE_SPANS, FEDSTEP_SCOPES, ROUND_SCOPES, PausableWallClock,
                       Recorder, VirtualClock, WallClock)

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def _op_names(hlo_text: str) -> set:
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _has_scope(names: set, scope: str) -> bool:
    """Some op's name stack holds ``scope`` as whole path components. The
    last component is the primitive's own name (``scatter`` is one)."""
    want = scope.split("/")
    for name in names:
        for part in name.split(";"):
            parts = part.split("/")[:-1]
            if any(parts[i:i + len(want)] == want for i in range(len(parts))):
                return True
    return False


@pytest.fixture(scope="module")
def engine_setup():
    x, y = synthetic_image_classification(n_samples=1000, seed=0, noise=1.0)
    part = partition_similarity(y, 8, 50, np.random.default_rng(0))
    data = FederatedDataset.from_partition(x, y, part)
    return data, make_topology("complete", 8), make_fnn((32,))


@pytest.mark.parametrize("bits", [8, 32])
def test_round_program_carries_every_round_scope(engine_setup, bits):
    data, topo, model = engine_setup
    eng = DFedRW(model, data, topo, DFedRWConfig(m_chains=4, k_walk=3, batch_size=16,
                                                 quant=QuantConfig(bits=bits)))
    state = eng.init_state(jax.random.PRNGKey(0))
    plan, bidx = eng.plan_walks(state)
    agg = eng.plan_aggregation(plan)
    inputs = eng.round_inputs(state, plan, bidx, agg, jax.random.PRNGKey(1))
    names = _op_names(eng.round_program(bits).lower(*inputs).compile().as_text())
    # the 32-bit wire sends plain rows: no quantize-dequantize scope exists
    expected = [s for s in ROUND_SCOPES if bits < 32 or not s.endswith("qdq")]
    missing = [s for s in expected if not _has_scope(names, s)]
    assert not missing, missing
    if bits == 32:
        assert not _has_scope(names, "walk/hop_qdq")
        assert not _has_scope(names, "aggregate/qdq")


_FED_STEP_SCOPES = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import json, re, sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp
    from repro.dist.gossip import GossipConfig
    from repro.dist.steps import make_fed_train_step
    from repro.launch.mesh import make_mesh
    from repro.models.config import ArchConfig

    cfg = ArchConfig(name="tiny", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=128)
    mesh = make_mesh((4, 1, 1), ("pod", "data", "model"))
    tok = jax.ShapeDtypeStruct((4, 2, 16), jnp.int32)
    out = {{}}
    for every in (1, 2):
        fn, _, abstract = make_fed_train_step(
            cfg, mesh, GossipConfig(axis="pod", topology="ring", every=every),
            remat=False, dtype=jnp.float32)
        with mesh:
            text = jax.jit(fn).lower(abstract, abstract, dict(tokens=tok, labels=tok),
                                     jnp.int32(0), jax.random.PRNGKey(0)).compile().as_text()
        out[every] = sorted(set(re.findall(r'op_name="([^"]*)"', text)))
    print("FED_STEP_NAMES " + json.dumps(out))
""")


def test_fed_step_carries_forward_backward_optimizer_gossip():
    """On a 4-pod mesh, with gossip every step and behind ``lax.cond``."""
    r = subprocess.run([sys.executable, "-c", _FED_STEP_SCOPES.format(src=SRC)],
                       capture_output=True, text=True, timeout=600)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("FED_STEP_NAMES ")]
    assert line, r.stdout[-2000:] + r.stderr[-2000:]
    for every, names in json.loads(line[0].split(" ", 1)[1]).items():
        comps = {c for n in names for part in n.split(";") for c in part.split("/")}
        forward, optimizer, gossip = FEDSTEP_SCOPES[:3]
        assert any(f"jvp({forward})" in c and "transpose" not in c for c in comps), every
        assert any(f"transpose(jvp({forward}))" in c for c in comps), every
        assert _has_scope(set(names), optimizer), every
        assert _has_scope(set(names), gossip), every
        ppermutes = [n for n in names if n.endswith("/ppermute")]
        assert ppermutes and all(_has_scope({n}, gossip) for n in ppermutes), every
        if every == "2":
            assert any("/cond/" in n and _has_scope({n}, gossip) for n in names)


def test_moe_fed_step_carries_the_layer_scopes():
    """The DeepSeek smoke model's pod step: MLA and the expert layer's
    parts carry their scopes, forward and backward."""
    from repro.configs import get_smoke
    from repro.dist.gossip import GossipConfig
    from repro.dist.steps import make_fed_train_step
    from repro.launch.mesh import make_mesh

    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"), jax.devices()[:1])
    fn, _, abstract = make_fed_train_step(get_smoke("deepseek-v2-lite-16b"), mesh,
                                          GossipConfig(axis="pod"), remat=True,
                                          dtype=jnp.float32)
    tok = jax.ShapeDtypeStruct((1, 2, 16), jnp.int32)
    with mesh:
        names = _op_names(jax.jit(fn).lower(abstract, abstract, dict(tokens=tok, labels=tok),
                                            jnp.int32(0), jax.random.PRNGKey(0))
                          .compile().as_text())
    missing = [s for s in FEDSTEP_SCOPES[3:] if not _has_scope(names, s)]
    assert not missing, missing
    assert any("transpose(jvp(forward))" in n and "moe/experts" in n for n in names)


def _host_events(logdir) -> list:
    from jax.profiler import ProfileData

    (path,) = [os.path.join(d, f) for d, _, fs in os.walk(logdir) for f in fs
               if f.endswith(".xplane.pb")]
    return [e for plane in ProfileData.from_file(path).planes
            if plane.name.startswith("/host") for line in plane.lines for e in line.events]


@pytest.mark.parametrize("clock", [WallClock, PausableWallClock])
def test_wall_clock_span_is_a_profiler_annotation(tmp_path, clock):
    rec = Recorder(clock=clock())
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("layer/outer", kind="x"):
            with rec.span("layer/inner"):
                jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = {e.name: e for e in _host_events(tmp_path)}
    outer, inner = events['layer/outer{kind="x"}'], events["layer/inner"]
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= outer.start_ns + outer.duration_ns
    # the recorder's own interval is unchanged by the annotation
    assert [e["name"] for e in rec.events] == ["layer/inner", 'layer/outer{kind="x"}']


def test_virtual_clock_span_writes_no_annotation(tmp_path):
    rec = Recorder(clock=VirtualClock(lambda: 3.0))
    jax.profiler.start_trace(str(tmp_path))
    try:
        with rec.span("sim/virtual_only"):
            jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    assert not [e for e in _host_events(tmp_path) if e.name.startswith("sim/")]
    assert rec.summary()["spans"]["sim/virtual_only"] == {"count": 1, "total_s": 0.0}


def test_engine_spans_nest_in_order(engine_setup):
    data, topo, model = engine_setup
    eng = DFedRW(model, data, topo, DFedRWConfig(m_chains=4, k_walk=3, batch_size=16,
                                                 quant=QuantConfig(bits=8)))
    rec = Recorder()
    eng.attach_obs(rec)
    key = jax.random.PRNGKey(2)
    state = eng.init_state(key)
    for _ in range(2):
        key, sub = jax.random.split(key)
        state, _ = eng.run_round(state, sub)
    plan, execute, dispatch, account, wait = ENGINE_SPANS
    spans = [e for e in rec.events if e["kind"] == "span"]
    assert [e["name"] for e in spans] == [plan, dispatch, account, wait, execute] * 2
    for r in range(2):
        p, d, a, w, x = spans[5 * r:5 * r + 5]
        assert p["t1"] <= x["t0"] <= d["t0"] <= d["t1"] <= a["t0"] <= a["t1"] \
            <= w["t0"] <= w["t1"] <= x["t1"]
    assert rec.value("engine/rounds") == 2.0


def test_engine_without_recorder_runs_the_same_round(engine_setup):
    """The spans are host bookkeeping: a round with and without a recorder
    leaves bit-identical device models."""
    data, topo, model = engine_setup
    cfg = DFedRWConfig(m_chains=4, k_walk=3, batch_size=16, quant=QuantConfig(bits=8))
    out = []
    for rec in (None, Recorder()):
        eng = DFedRW(model, data, topo, cfg)
        if rec is not None:
            eng.attach_obs(rec)
        state = eng.init_state(jax.random.PRNGKey(3))
        state, met = eng.run_round(state, jax.random.PRNGKey(4))
        out.append((np.asarray(state.device_params), met.train_loss))
    np.testing.assert_array_equal(out[0][0], out[1][0])
    assert out[0][1] == out[1][1]
