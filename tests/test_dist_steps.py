"""Distribution-layer step builders: numerics on the host device plus
lowering/semantics checks that need multi-device subprocesses."""
import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.launch.mesh import make_mesh
from repro.models.config import ArchConfig, MoEConfig
from repro.models import transformer as T

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))

TINY = ArchConfig(name="tiny", n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                  d_ff=128, vocab=128)


def test_train_step_learns_single_device():
    from repro.dist.steps import make_train_step

    mesh = make_mesh((1, 1), ("data", "model"))
    step_fn, _ = make_train_step(TINY, mesh, lr_r=2.0, remat=False)
    params = T.init_params(TINY, jax.random.PRNGKey(0), jnp.float32)
    vel = jax.tree_util.tree_map(jnp.zeros_like, params)
    jitted = jax.jit(step_fn)
    rng = np.random.default_rng(0)
    losses = []
    with mesh:
        for step in range(30):
            t0 = rng.integers(0, TINY.vocab, size=(8, 1))
            seq = [t0]
            for _ in range(16):
                seq.append((5 * seq[-1] + 3) % TINY.vocab)
            toks = np.concatenate(seq, axis=-1)
            batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                     "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
            params, vel, loss = jitted(params, vel, batch, jnp.int32(step))
            losses.append(float(loss))
    assert losses[-1] < losses[0] - 1.0, (losses[0], losses[-1])


def test_opt_specs_shard_where_params_replicate():
    """Optimizer-state specs: leaves the param rules shard keep the exact
    same spec (the elementwise update stays collective-free); leaves the
    param rules replicate (1-D scales/biases, indivisible fallbacks) are
    ZeRO-style data-sharded on the first divisible dim."""
    from jax.sharding import PartitionSpec as P

    from repro.dist.steps import opt_specs
    from repro.dist.sharding import param_specs

    mesh = jax.sharding.AbstractMesh((1, 2, 1), ("pod", "data", "model"))
    params = T.abstract_params(TINY, jnp.float32)
    p_specs = param_specs(params, mesh)
    o_specs = opt_specs(params, mesh)
    is_spec = lambda s: isinstance(s, P)
    flat_p, _ = jax.tree_util.tree_flatten_with_path(p_specs, is_leaf=is_spec)
    flat_o = dict(jax.tree_util.tree_flatten_with_path(
        o_specs, is_leaf=is_spec)[0])
    flat_l = dict(jax.tree_util.tree_flatten_with_path(params)[0])
    upgraded = 0
    for path, pspec in flat_p:
        ospec, shape = flat_o[path], tuple(flat_l[path].shape)
        if any(ax is not None for ax in pspec):
            assert ospec == pspec, (path, pspec, ospec)
        elif any(d % 2 == 0 for d in shape):
            assert any(ax == "data" for ax in ospec), (path, shape, ospec)
            upgraded += 1
    assert upgraded > 0  # TINY has even-dim norm scales: they must shard

    # fed_axis prepends the pod stacking axis like param_specs does
    o_fed = opt_specs(params, mesh, fed_axis="pod")
    leaf = jax.tree_util.tree_leaves(
        o_fed, is_leaf=lambda s: isinstance(s, P))[0]
    assert leaf[0] == "pod"


def test_opt_specs_state_learns_single_device():
    """A train step whose velocity is placed by opt_specs (differently from
    the params) still optimizes: the sharded elementwise update is
    numerics-neutral."""
    from repro.dist.sharding import named
    from repro.dist.steps import make_train_step, opt_specs

    mesh = make_mesh((1, 1), ("data", "model"))
    step_fn, p_specs = make_train_step(TINY, mesh, lr_r=2.0, remat=False)
    params = T.init_params(TINY, jax.random.PRNGKey(0), jnp.float32)
    vel = jax.tree_util.tree_map(jnp.zeros_like, params)
    vel = jax.device_put(vel, named(opt_specs(params, mesh), mesh))
    jitted = jax.jit(step_fn)
    rng = np.random.default_rng(0)
    losses = []
    with mesh:
        for step in range(20):
            toks = np.cumsum(rng.integers(1, 5, size=(8, 18)), axis=-1) % TINY.vocab
            batch = {"tokens": jnp.asarray(toks[:, :-1], jnp.int32),
                     "labels": jnp.asarray(toks[:, 1:], jnp.int32)}
            params, vel, loss = jitted(params, vel, batch, jnp.int32(step))
            losses.append(float(loss))
    assert losses[-1] < losses[0] - 0.5, (losses[0], losses[-1])


@pytest.mark.parametrize("bias", [0.0, 30.0])
def test_moe_dropless_equivalence(bias):
    """The sorted, grouped dispatch computes each token's top-k experts,
    weighted by its gates, with no assignment dropped: also where a biased
    router sends every token to the same two experts (a capacity buffer
    would have dropped most of them)."""
    from repro.models import layers as L

    cfg = ArchConfig(name="m", n_layers=1, d_model=32, n_heads=4, n_kv_heads=4,
                     d_ff=64, vocab=64, ffn_pattern=("moe",),
                     moe=MoEConfig(n_experts=4, top_k=2, n_shared=1))
    key = jax.random.PRNGKey(0)
    p = L.init_moe(key, cfg, jnp.float32)
    p["router"] = p["router"].at[:, 1:3].add(bias)
    x = jax.random.normal(key, (2, 32, 32), jnp.float32) + 1.0    # sum(x) > 0: biased logits
    with jax.default_matmul_precision("highest"):
        y, _ = L.moe_apply(p, x, cfg)
        probs = jax.nn.softmax(x @ p["router"], axis=-1)
        gates, idx = jax.lax.top_k(probs, 2)
        gates = gates / gates.sum(-1, keepdims=True)
        experts = jnp.stack([L.ffn_apply({n: p[n][e] for n in ("w_gate", "w_up", "w_down")}, x)
                             for e in range(4)], axis=2)          # (b, l, E, d)
        want = jnp.sum(jnp.take_along_axis(experts, idx[..., None], axis=2)
                       * gates[..., None], axis=2) + L.ffn_apply(p["shared"], x)
    if bias:
        assert bool(jnp.all(jnp.sort(idx, axis=-1) == jnp.array([1, 2])))
    # float32 on the CPU: only summation order differs
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-5, rtol=1e-5)


def test_optimize_cfg_rules():
    import importlib
    D = importlib.import_module("repro.launch.dryrun")
    from repro.configs import get_arch

    q25 = D.optimize_cfg(get_arch("qwen2.5-32b"))
    assert q25.attn_batch_parallel  # 40 heads % 16 != 0
    q2 = D.optimize_cfg(get_arch("qwen2-72b"))
    assert not q2.attn_batch_parallel  # 64 heads divides
    gk = get_arch("grok-1-314b")
    assert D.optimize_cfg(gk) == gk  # 48 heads divides; the MoE is dropless
    mm = D.optimize_cfg(get_arch("mamba2-130m"))
    assert mm == get_arch("mamba2-130m")  # nothing to do


def test_fed_train_step_scheduled_matches_static():
    """The trace-driven fed step (gossip trigger as a data operand — fed one
    element of ``SimTrace.gossip_flags()`` per step) must be bit-identical
    to the static ``gossip.every`` modulo it replaces."""
    from repro.dist.gossip import GossipConfig
    from repro.dist.steps import make_fed_train_step

    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    gossip = GossipConfig(axis="pod", topology="ring", every=2)
    static_fn, _, _ = make_fed_train_step(
        TINY, mesh, gossip, lr_r=2.0, remat=False, dtype=jnp.float32)
    sched_fn, _, _ = make_fed_train_step(
        TINY, mesh, gossip, lr_r=2.0, remat=False, dtype=jnp.float32,
        scheduled=True)

    base = T.init_params(TINY, jax.random.PRNGKey(0), jnp.float32)
    stack = jax.tree_util.tree_map(lambda l: l[None].copy(), base)
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(4):
        toks = rng.integers(0, TINY.vocab, size=(1, 4, 17))
        batches.append({"tokens": jnp.asarray(toks[..., :-1], jnp.int32),
                        "labels": jnp.asarray(toks[..., 1:], jnp.int32)})
    # the schedule a recorded trace exports: gossip at every window end,
    # here every=2 steps (same pattern SimTrace.gossip_flags() yields for
    # k_walk=2)
    flags = [(s + 1) % gossip.every == 0 for s in range(4)]

    def run(fn, scheduled):
        params = jax.tree_util.tree_map(jnp.copy, stack)
        vel = jax.tree_util.tree_map(jnp.zeros_like, params)
        key = jax.random.PRNGKey(7)
        jitted = jax.jit(fn)
        with mesh:
            for s, batch in enumerate(batches):
                key, sub = jax.random.split(key)
                if scheduled:
                    params, vel, _ = jitted(params, vel, batch, jnp.int32(s),
                                            jnp.bool_(flags[s]), sub)
                else:
                    params, vel, _ = jitted(params, vel, batch, jnp.int32(s),
                                            sub)
        return params

    for a, b in zip(jax.tree_util.tree_leaves(run(static_fn, False)),
                    jax.tree_util.tree_leaves(run(sched_fn, True))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


_SCHEDULED_FED_STEP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.gossip import GossipConfig
    from repro.dist.sharding import batch_specs, named
    from repro.dist.steps import make_fed_train_step
    from repro.models.config import ArchConfig
    from repro.models import transformer as T

    cfg = ArchConfig(name="tiny", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=128)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2, 1), ("pod", "data", "model"))
    gossip = GossipConfig(axis="pod", topology="ring", every=2)
    static_fn, p_specs, _ = make_fed_train_step(
        cfg, mesh, gossip, lr_r=2.0, remat=False, dtype=jnp.float32)
    sched_fn, _, _ = make_fed_train_step(
        cfg, mesh, gossip, lr_r=2.0, remat=False, dtype=jnp.float32,
        scheduled=True)

    base = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    stack = jax.tree_util.tree_map(
        lambda l: jnp.broadcast_to(l, (4, *l.shape)).copy(), base)
    rng = np.random.default_rng(0)
    batches = []
    for _ in range(4):
        toks = rng.integers(0, cfg.vocab, size=(4, 4, 17))
        batches.append(dict(tokens=jnp.asarray(toks[..., :-1], jnp.int32),
                            labels=jnp.asarray(toks[..., 1:], jnp.int32)))
    flags = [(s + 1) % gossip.every == 0 for s in range(4)]

    def run(fn, scheduled):
        params = jax.device_put(stack, named(p_specs, mesh))
        vel = jax.tree_util.tree_map(jnp.zeros_like, params)
        b_shard = named(batch_specs(batches[0], mesh, fed_axis="pod"), mesh)
        key = jax.random.PRNGKey(7)
        jitted = jax.jit(fn)
        with mesh:
            for s, batch in enumerate(batches):
                batch = jax.device_put(batch, b_shard)
                key, sub = jax.random.split(key)
                if scheduled:
                    params, vel, _ = jitted(params, vel, batch, jnp.int32(s),
                                            jnp.bool_(flags[s]), sub)
                else:
                    params, vel, _ = jitted(params, vel, batch, jnp.int32(s),
                                            sub)
        return params

    for a, b in zip(jax.tree_util.tree_leaves(run(static_fn, False)),
                    jax.tree_util.tree_leaves(run(sched_fn, True))):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("SCHEDULED_FED_STEP_OK")
""")


@pytest.mark.slow
def test_fed_train_step_scheduled_matches_static_multidevice():
    """Same bit-identity on a real 4-pod mesh: the cond-gated gossip mix
    lowers to the same collectives as the modulo-gated one."""
    code = _SCHEDULED_FED_STEP.format(src=SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert "SCHEDULED_FED_STEP_OK" in r.stdout, \
        r.stdout[-2000:] + r.stderr[-2000:]


_GOSSIP_STEP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist.gossip import GossipConfig
    from repro.dist.sharding import named
    from repro.dist.steps import make_gossip_step
    from repro.models.config import ArchConfig
    from repro.models import transformer as T

    cfg = ArchConfig(name="tiny", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=128)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2, 1), ("pod", "data", "model"))
    gossip = GossipConfig(axis="pod", topology="ring")
    gstep, p_specs, fed_abs = make_gossip_step(cfg, mesh, gossip)

    base = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    # give each pod a different model: pod g = base * (g+1)
    params = jax.tree_util.tree_map(
        lambda l: jnp.stack([l * (g + 1) for g in range(4)]), base)
    params = jax.device_put(params, named(p_specs, mesh))
    with mesh:
        mixed = jax.jit(gstep)(params, jax.random.PRNGKey(1))
    leaf = jax.tree_util.tree_leaves(mixed)[0]
    base_leaf = jax.tree_util.tree_leaves(base)[0]
    # ring mix of scales [1,2,3,4] with uniform 1/3 weights over self/+1/-1:
    expect = np.array([(1 + 2 + 4) / 3, (2 + 3 + 1) / 3, (3 + 4 + 2) / 3, (4 + 1 + 3) / 3])
    got = np.asarray(leaf) / np.maximum(np.abs(np.asarray(base_leaf)), 1e-9)[None]
    sign = np.sign(np.asarray(base_leaf))[None]
    axes = tuple(range(1, got.ndim))
    np.testing.assert_allclose(np.nanmedian(got * sign, axis=axes), expect, rtol=1e-4)
    # global mean preserved (doubly stochastic)
    np.testing.assert_allclose(
        np.asarray(leaf).mean(0), np.asarray(base_leaf) * 2.5, rtol=1e-4)
    print("GOSSIP_STEP_OK")
""")


@pytest.mark.slow
def test_gossip_step_semantics_multidevice():
    code = _GOSSIP_STEP.format(src=SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       timeout=600)
    assert "GOSSIP_STEP_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]


_OPT_SPECS_STEP = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import PartitionSpec as P
    from repro.dist.sharding import named, opt_specs, param_specs
    from repro.dist.steps import make_train_step
    from repro.models.config import ArchConfig
    from repro.models import transformer as T

    cfg = ArchConfig(name="tiny", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=128)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((8, 1), ("data", "model"))
    step_fn, p_specs = make_train_step(cfg, mesh, lr_r=2.0, remat=False)
    o_specs = opt_specs(T.abstract_params(cfg), mesh)
    # the upgrade path must actually fire on a size-8 data axis: at least
    # one leaf the param rules replicate is now data-sharded
    flat_p = jax.tree_util.tree_leaves(p_specs, is_leaf=lambda s: isinstance(s, P))
    flat_o = jax.tree_util.tree_leaves(o_specs, is_leaf=lambda s: isinstance(s, P))
    upgraded = sum(1 for ps, os_ in zip(flat_p, flat_o)
                   if all(a is None for a in ps) and any(a == "data" for a in os_))
    assert upgraded > 0, "ZeRO upgrade never fired"

    def batch_for(step):
        rng = np.random.default_rng(step)
        toks = rng.integers(0, cfg.vocab, size=(8, 17))
        return dict(tokens=jnp.asarray(toks[:, :-1], jnp.int32),
                    labels=jnp.asarray(toks[:, 1:], jnp.int32))

    def run(vel_specs):
        params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        params = jax.device_put(params, named(p_specs, mesh))
        vel = jax.tree_util.tree_map(jnp.zeros_like, params)
        vel = jax.device_put(vel, named(vel_specs, mesh))
        jitted = jax.jit(step_fn)
        with mesh:
            for step in range(3):
                params, vel, loss = jitted(params, vel, batch_for(step),
                                           jnp.int32(step))
        return params, vel

    p_ref, _ = run(p_specs)      # velocity sharded like the params
    p_opt, v_opt = run(o_specs)  # velocity ZeRO-sharded by opt_specs
    for a, b in zip(jax.tree_util.tree_leaves(p_ref),
                    jax.tree_util.tree_leaves(p_opt)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OPT_SPECS_STEP_OK")
""")


@pytest.mark.slow
def test_opt_specs_state_multidevice_numerics_neutral():
    """On a real size-8 data axis the ZeRO upgrade fires for replicated
    leaves, and a train step whose velocity is placed by opt_specs produces
    BIT-identical params to one whose velocity shards like the params —
    the state sharding is free."""
    code = _OPT_SPECS_STEP.format(src=SRC)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600)
    assert "OPT_SPECS_STEP_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
