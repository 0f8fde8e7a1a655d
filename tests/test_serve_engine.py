"""Continuous-batching serve engine: slot reuse, stop conditions,
mixed-length batches, scheduler semantics, and the sharded path.

Runs on however many devices the process has: tier-1 sees one; the
`tools/check.sh --serve` lane re-runs under
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the same tests
exercise the mesh-sharded decode/prefill programs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models.config import ArchConfig, MLAConfig, MoEConfig, SSMConfig
from repro.models import transformer as T
from repro.serve import EngineConfig, Phase, Request, ServeEngine
from repro.serve.scheduler import FCFSScheduler, stop_reason

DENSE = ArchConfig(name="d", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                   d_ff=128, vocab=64, qkv_bias=True)
SSM = ArchConfig(name="s", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                 d_ff=0, vocab=64, block_pattern=("mamba",), ffn_pattern=("none",),
                 ssm=SSMConfig(state_dim=16, head_dim=16, chunk=8), tie_embeddings=True)
HYBRID = ArchConfig(name="h", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                    d_ff=128, vocab=64, block_pattern=("mamba", "attn"),
                    ffn_pattern=("dense", "moe"),
                    moe=MoEConfig(n_experts=4, top_k=2),
                    ssm=SSMConfig(state_dim=16, head_dim=16, chunk=8))
MLA = ArchConfig(name="m", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                 d_ff=128, vocab=64, attn_type="mla",
                 mla=MLAConfig(kv_lora_rank=32, qk_nope_dim=16, qk_rope_dim=8,
                               v_head_dim=16))

MAX_LEN = 48


def _params(cfg, seed=0):
    return T.init_params(cfg, jax.random.PRNGKey(seed), jnp.float32)


def _requests(cfg, n, rng, max_prompt=16, max_gen=10, eos_id=-1, spread=0):
    reqs = []
    for i in range(n):
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, size=(int(rng.integers(2, max_prompt)),)),
            max_tokens=int(rng.integers(2, max_gen)), eos_id=eos_id,
            arrival_step=int(rng.integers(0, spread + 1)) if spread else 0))
    return reqs


def _sequential(cfg, params, req, max_len=MAX_LEN):
    """Token-at-a-time reference: the engine must match this bit-for-bit
    at temperature 0 (same argmax over the same model)."""
    cache = T.init_cache(cfg, 1, max_len, jnp.float32)
    step = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t))
    logits = None
    for t in range(len(req.prompt)):
        logits, cache = step(params, cache, jnp.asarray(req.prompt[None, t:t + 1]))
    out = []
    for _ in range(req.max_tokens):
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        if req.eos_id >= 0 and tok == req.eos_id:
            break
        logits, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32))
    return out


def _mesh():
    """Whatever this process offers: (1,1) under tier-1, (4,2) in the
    8-device serve lane."""
    n = len(jax.devices())
    model = 2 if n % 2 == 0 and n > 1 else 1
    return make_mesh((n // model, model), ("data", "model"))


# ---------------------------------------------------------------- scheduler
def test_fcfs_admission_order_and_arrival_gating():
    s = FCFSScheduler()
    for rid, arr in [(0, 0), (1, 5), (2, 0)]:
        s.submit(Request(rid=rid, prompt=np.array([1]), arrival_step=arr))
    got = s.admit([0, 1, 2, 3], now_step=0)
    # strict FCFS: rid 1 has not arrived and blocks rid 2 behind it
    assert [st.request.rid for st in got] == [0]
    got = s.admit([1, 2], now_step=5)
    assert [st.request.rid for st in got] == [1, 2]
    assert [st.slot for st in got] == [1, 2]


def test_stop_reasons():
    req = Request(rid=0, prompt=np.array([1]), max_tokens=3, eos_id=9)
    assert stop_reason(req, [1, 2]) == ""
    assert stop_reason(req, [1, 9]) == "eos"
    assert stop_reason(req, [1, 2, 3]) == "max_tokens"


def test_request_validation():
    with pytest.raises(ValueError):
        Request(rid=0, prompt=np.array([]))
    with pytest.raises(ValueError):
        Request(rid=0, prompt=np.array([1]), max_tokens=0)
    eng = ServeEngine(DENSE, _params(DENSE),
                      EngineConfig(max_concurrency=2, max_len=8))
    with pytest.raises(ValueError, match="exceeds"):
        eng.submit(Request(rid=0, prompt=np.arange(6), max_tokens=6))
    eng.submit(Request(rid=1, prompt=np.arange(4), max_tokens=4))
    with pytest.raises(ValueError, match="duplicate"):
        eng.submit(Request(rid=1, prompt=np.arange(4), max_tokens=4))


# ------------------------------------------------------------------- engine
@pytest.mark.parametrize("cfg", [DENSE, SSM, HYBRID, MLA], ids=lambda c: c.name)
def test_engine_matches_sequential_mixed_lengths(cfg):
    """Mixed-length staggered requests through more work than slots: every
    request's output is bit-identical to the sequential decode path, and
    slot reuse after retirement never retraces."""
    params = _params(cfg)
    rng = np.random.default_rng(3)
    reqs = _requests(cfg, 9, rng, spread=6)
    eng = ServeEngine(cfg, params,
                      EngineConfig(max_concurrency=3, max_len=MAX_LEN, chunk=5),
                      mesh=_mesh())
    results = eng.run(reqs)
    assert len(results) == len(reqs)
    # 9 requests through 3 slots => every slot was reused
    assert eng.metrics.summary()["requests_finished"] == 9
    assert eng.trace_counts == {"prefill": 1, "decode": 1}
    for st in results:
        assert st.generated == _sequential(cfg, params, st.request), st.request.rid


def test_slot_reuse_resets_recurrent_state():
    """A retired request's mamba conv/ssm state must not leak into the next
    occupant of its slot: run the same request twice, once on a cold engine
    and once after the slot served an unrelated request."""
    cfg = SSM
    params = _params(cfg)
    rng = np.random.default_rng(5)
    probe = Request(rid=10, prompt=rng.integers(0, cfg.vocab, 9), max_tokens=6)
    warm = Request(rid=0, prompt=rng.integers(0, cfg.vocab, 12), max_tokens=4)
    cold = ServeEngine(cfg, params, EngineConfig(max_concurrency=1, max_len=MAX_LEN))
    (cold_res,) = cold.run([Request(**{**probe.__dict__})])
    eng = ServeEngine(cfg, params, EngineConfig(max_concurrency=1, max_len=MAX_LEN))
    res = eng.run([warm, Request(**{**probe.__dict__, "rid": 11, "arrival_step": 0})])
    reused = [st for st in res if st.request.rid == 11][0]
    assert reused.generated == cold_res.generated
    assert eng.trace_counts == {"prefill": 1, "decode": 1}


def test_eos_stop_retires_early_and_frees_slot():
    cfg = DENSE
    params = _params(cfg)
    rng = np.random.default_rng(1)
    base = _requests(cfg, 4, rng, max_gen=12)
    # discover a token the first request actually emits, then use it as EOS
    eng = ServeEngine(cfg, params, EngineConfig(max_concurrency=2, max_len=MAX_LEN))
    plain = eng.run([Request(**st.__dict__) for st in base])
    target = next(st for st in plain if len(st.generated) >= 3)
    eos = target.generated[2]
    eos_reqs = [Request(**{**r.__dict__, "eos_id": eos}) for r in base]
    eng2 = ServeEngine(cfg, params, EngineConfig(max_concurrency=2, max_len=MAX_LEN))
    stopped = eng2.run(eos_reqs)
    st = next(s for s in stopped if s.request.rid == target.request.rid)
    assert st.stop == "eos" and st.generated[-1] == eos
    assert len(st.generated) == 3
    for s in stopped:  # every request still matches the sequential path
        assert s.generated == _sequential(cfg, params, s.request), s.request.rid
    # early retirement freed capacity: engine never waits for the slowest
    assert eng2.metrics.decode_steps <= eng.metrics.decode_steps


def test_engine_metrics_accounting():
    cfg = DENSE
    params = _params(cfg)
    rng = np.random.default_rng(2)
    reqs = _requests(cfg, 5, rng, spread=4)
    eng = ServeEngine(cfg, params,
                      EngineConfig(max_concurrency=2, max_len=MAX_LEN, chunk=4))
    results = eng.run(reqs)
    s = eng.metrics.summary()
    assert s["generated_tokens"] == sum(len(st.generated) for st in results)
    assert s["prompt_tokens"] == sum(len(r.prompt) for r in reqs)
    assert s["engine_steps"] == (s["prefill_chunks"] + s["decode_steps"]
                                 + s["idle_steps"])
    for st in results:
        m = eng.metrics.requests[st.request.rid]
        assert m.n_generated == len(st.generated)
        assert m.first_token_wall >= m.eligible_wall
        assert m.finish_wall >= m.first_token_wall
        assert m.ttft_s >= 0 and m.tpot_s >= 0
        assert m.admit_step >= m.arrival_step


def test_engine_sharded_cache_layout():
    """The engine's cache rows really are per-request slots: after a run,
    positions of freed slots reset on reuse and the cache shape never
    changed (no reshape-based batching)."""
    cfg = DENSE
    params = _params(cfg)
    eng = ServeEngine(cfg, params,
                      EngineConfig(max_concurrency=4, max_len=MAX_LEN),
                      mesh=_mesh())
    shape0 = jax.tree_util.tree_map(lambda l: l.shape, eng.cache)
    rng = np.random.default_rng(4)
    eng.run(_requests(cfg, 6, rng))
    assert jax.tree_util.tree_map(lambda l: l.shape, eng.cache) == shape0
    assert all(st is None for st in eng._slots)


def test_serve_arg_specs():
    from jax.sharding import PartitionSpec as P

    from repro.dist.sharding import serve_arg_specs

    mesh = jax.sharding.AbstractMesh((4, 2), ("data", "model"))
    args = {"token": jax.ShapeDtypeStruct((8, 1), jnp.int32),
            "positions": jax.ShapeDtypeStruct((8,), jnp.int32),
            "odd": jax.ShapeDtypeStruct((3,), jnp.int32)}
    specs = serve_arg_specs(args, mesh)
    assert specs["token"] == P("data", None)
    assert specs["positions"] == P("data")
    assert specs["odd"] == P(None)  # indivisible slot dim replicates


def test_encdec_engine_matches_sequential():
    """enc-dec serving: the per-slot encoder cache is filled at admission
    and cross-attention reads the right slot's encoder output — outputs
    stay bit-identical to the sequential path, including slot reuse."""
    cfg = ArchConfig(name="ed", n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
                     d_ff=128, vocab=64, enc_dec=True, n_enc_layers=2,
                     frontend="audio", frontend_tokens=8)
    params = _params(cfg)
    rng = np.random.default_rng(6)
    reqs = []
    for i in range(5):
        reqs.append(Request(
            rid=i, prompt=rng.integers(0, cfg.vocab, size=(int(rng.integers(2, 8)),)),
            max_tokens=int(rng.integers(2, 6)),
            embeds=rng.normal(size=(8, cfg.d_model)).astype(np.float32)))
    eng = ServeEngine(cfg, params,
                      EngineConfig(max_concurrency=2, max_len=MAX_LEN, chunk=4))
    results = eng.run(reqs)
    assert len(results) == 5 and eng.trace_counts["encode"] == 1

    import jax.numpy as jnp_
    from repro.models.transformer import _run_encoder

    step = jax.jit(lambda p, c, t: T.decode_step(cfg, p, c, t))
    for st in results:
        req = st.request
        cache = T.init_cache(cfg, 1, MAX_LEN, jnp.float32, enc_len=8)
        cache["enc_out"] = _run_encoder(cfg, params, jnp_.asarray(req.embeds)[None],
                                        remat=False)
        logits = None
        for t in range(len(req.prompt)):
            logits, cache = step(params, cache, jnp.asarray(req.prompt[None, t:t + 1]))
        ref = []
        for _ in range(req.max_tokens):
            tok = int(jnp.argmax(logits[0, -1]))
            ref.append(tok)
            logits, cache = step(params, cache, jnp.asarray([[tok]], jnp.int32))
        assert st.generated == ref, req.rid
    # enc-dec requests without embeds are rejected up front
    with pytest.raises(ValueError, match="embeds"):
        eng.submit(Request(rid=99, prompt=np.array([1]), max_tokens=2))
