"""Checkpoint substrate tests, including per-pod stacked federated state
(the first slice of the ROADMAP multi-host item: restore-then-continue
trajectory equality for make_fed_train_step)."""
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import latest_step, load_checkpoint, save_checkpoint
from repro.launch.mesh import make_mesh

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_roundtrip(tmp_path):
    tree = {"a": jnp.arange(6.0).reshape(2, 3), "b": [jnp.ones(4), jnp.zeros((2, 2))],
            "c": {"d": jnp.array(3)}}
    save_checkpoint(str(tmp_path), 7, tree, metrics={"loss": 1.5})
    template = jax.tree_util.tree_map(lambda x: jnp.zeros_like(x), tree)
    back, meta = load_checkpoint(str(tmp_path), template)
    assert meta["step"] == 7 and meta["metrics"]["loss"] == 1.5
    for a, b in zip(jax.tree_util.tree_leaves(tree), jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_latest_step(tmp_path):
    assert latest_step(str(tmp_path)) is None
    save_checkpoint(str(tmp_path), 3, {"x": jnp.zeros(2)})
    save_checkpoint(str(tmp_path), 12, {"x": jnp.ones(2)})
    assert latest_step(str(tmp_path)) == 12
    back, meta = load_checkpoint(str(tmp_path), {"x": jnp.zeros(2)})
    assert meta["step"] == 12 and float(back["x"][0]) == 1.0


def test_shape_mismatch_raises(tmp_path):
    save_checkpoint(str(tmp_path), 1, {"x": jnp.zeros((2, 2))})
    with pytest.raises(AssertionError):
        load_checkpoint(str(tmp_path), {"x": jnp.zeros((3,))})


def test_model_params_roundtrip(tmp_path):
    from repro.configs import get_smoke
    from repro.models import transformer as T

    cfg = get_smoke("yi-6b")
    params = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    save_checkpoint(str(tmp_path), 100, params)
    back, _ = load_checkpoint(str(tmp_path), params)
    a = jax.tree_util.tree_leaves(params)[3]
    b = jax.tree_util.tree_leaves(back)[3]
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_per_pod_stacked_fed_state_roundtrip(tmp_path):
    """The fed deployment's whole mutable state — per-pod stacked params
    (leading pod dim) + the velocity mirror + the step counter — survives a
    save/load cycle bit-exactly, and the restored stack device_puts onto the
    pod-axis shardings of dist.sharding (what a multi-host relaunch does)."""
    from repro.dist.sharding import named, opt_specs, param_specs
    from repro.models import transformer as T
    from repro.models.config import ArchConfig

    cfg = ArchConfig(name="tiny", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=128)
    base = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
    g = 4
    params = jax.tree_util.tree_map(
        lambda l: jnp.stack([l * (i + 1) for i in range(g)]), base)
    vel = jax.tree_util.tree_map(
        lambda l: jnp.ones((g, *l.shape), l.dtype) * 0.25, base)
    save_checkpoint(str(tmp_path), 11, {"params": params, "vel": vel},
                    metrics={"loss": 2.0})
    template = jax.tree_util.tree_map(
        jnp.zeros_like, {"params": params, "vel": vel})
    back, meta = load_checkpoint(str(tmp_path), template)
    assert meta["step"] == 11
    for a, b in zip(jax.tree_util.tree_leaves({"params": params, "vel": vel}),
                    jax.tree_util.tree_leaves(back)):
        assert a.shape[0] == g
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # restored stacks place onto the fed-axis shardings (pod axis size 1
    # on this host; the specs are the same ones a real pod mesh uses)
    mesh = make_mesh((1, 1, 1), ("pod", "data", "model"))
    placed_p = jax.device_put(back["params"],
                              named(param_specs(base, mesh, fed_axis="pod"), mesh))
    placed_v = jax.device_put(back["vel"],
                              named(opt_specs(base, mesh, fed_axis="pod"), mesh))
    for a, b in zip(jax.tree_util.tree_leaves(placed_p),
                    jax.tree_util.tree_leaves(params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert jax.tree_util.tree_leaves(placed_v)[0].shape[0] == g


_FED_RESTORE_CONTINUE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from repro.checkpoint import load_checkpoint, save_checkpoint
    from repro.dist.gossip import GossipConfig
    from repro.dist.sharding import named
    from repro.dist.steps import make_fed_train_step
    from repro.models.config import ArchConfig
    from repro.models import transformer as T

    cfg = ArchConfig(name="tiny", n_layers=2, d_model=64, n_heads=4,
                     n_kv_heads=2, d_ff=128, vocab=128)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4, 2, 1), ("pod", "data", "model"))
    gossip = GossipConfig(axis="pod", topology="ring", every=2)
    step_fn, p_specs, _ = make_fed_train_step(cfg, mesh, gossip, remat=False,
                                              dtype=jnp.float32)
    jitted = jax.jit(step_fn)
    g = 4

    def init():
        base = T.init_params(cfg, jax.random.PRNGKey(0), jnp.float32)
        params = jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l, (g, *l.shape)).copy(), base)
        params = jax.device_put(params, named(p_specs, mesh))
        return params, jax.tree_util.tree_map(jnp.zeros_like, params)

    def batch_for(step):
        rng = np.random.default_rng(100 + step)
        toks = rng.integers(0, cfg.vocab, size=(g, 4, 17))
        return dict(tokens=jnp.asarray(toks[..., :-1], jnp.int32),
                    labels=jnp.asarray(toks[..., 1:], jnp.int32))

    def run(params, vel, lo, hi):
        with mesh:
            for step in range(lo, hi):
                key = jax.random.fold_in(jax.random.PRNGKey(7), step)
                params, vel, _ = jitted(params, vel, batch_for(step),
                                        jnp.int32(step), key)
        return params, vel

    ckpt = sys.argv[1]
    # run A: 6 uninterrupted steps
    pa, va = run(*init(), 0, 6)
    # run B: 3 steps, checkpoint, restore into fresh buffers, 3 more
    pb, vb = run(*init(), 0, 3)
    save_checkpoint(ckpt, 3, dict(params=pb, vel=vb))
    fresh_p, fresh_v = init()
    restored, meta = load_checkpoint(
        ckpt, dict(params=fresh_p, vel=fresh_v))
    rp = jax.device_put(restored["params"], named(p_specs, mesh))
    rv = jax.device_put(restored["vel"],
                        jax.tree_util.tree_map(lambda l: l.sharding, fresh_v))
    pb, vb = run(rp, rv, meta["step"], 6)
    for a, b in zip(jax.tree_util.tree_leaves(pa), jax.tree_util.tree_leaves(pb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for a, b in zip(jax.tree_util.tree_leaves(va), jax.tree_util.tree_leaves(vb)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("FED_RESTORE_OK")
""")


@pytest.mark.slow
def test_fed_restore_then_continue_multidevice(tmp_path):
    """Restore-then-continue trajectory equality for the 4-pod fed train
    step (8 virtual devices, gossip every 2 steps crossing the checkpoint
    boundary): 3 steps + checkpoint + restore + 3 steps is BIT-identical to
    6 uninterrupted steps, params and velocity both."""
    code = _FED_RESTORE_CONTINUE.format(src=SRC)
    r = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                       capture_output=True, text=True, timeout=600)
    assert "FED_RESTORE_OK" in r.stdout, r.stdout[-2000:] + r.stderr[-2000:]
