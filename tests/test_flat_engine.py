"""Flat-buffer round engine: parity against the reference (seed) engine,
vectorized-scatter tie-breaking semantics, codec round trips, retrace guard,
and the gamma-hat dead-chain fix."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DFedRW, DFedRWConfig, QuantConfig, make_topology
from repro.core.dfedrw import gamma_hat_from_traj
from repro.core.flatten import (
    LANES,
    elect_writers,
    flatten_tree,
    make_flat_spec,
    unflatten_tree,
)
from repro.core.heterogeneity import partition_similarity
from repro.data import FederatedDataset, synthetic_image_classification
from repro.kernels.rowmerge import merge_rows
from repro.models import make_fnn


@pytest.fixture(scope="module")
def setup():
    x, y = synthetic_image_classification(n_samples=2000, seed=0, noise=1.0)
    part = partition_similarity(y, 10, 50, np.random.default_rng(0))
    data = FederatedDataset.from_partition(x, y, part)
    topo = make_topology("complete", 10)
    model = make_fnn((64,))
    return data, topo, model


def _run_pair(data, topo, model, cfg, rounds=3):
    ref = DFedRW(model, data, topo, dataclasses.replace(cfg, engine="reference"))
    fla = DFedRW(model, data, topo, dataclasses.replace(cfg, engine="flat"))
    key = jax.random.PRNGKey(0)
    sr = ref.init_state(key)
    sf = fla.init_state(key)
    out = []
    for _ in range(rounds):
        key, sub = jax.random.split(key)
        sr, mr = ref.run_round(sr, sub)
        sf, mf = fla.run_round(sf, sub)
        # the next flat round consumes (donates) sf's device matrix
        snap = dataclasses.replace(sf, device_params=np.asarray(sf.device_params))
        out.append((sr, mr, snap, mf))
    return ref, fla, out


def test_parity_bits32_bit_exact(setup):
    """fp32 round trajectories of the two engines are BIT-identical in the
    state that propagates (device params) and exact in comm accounting; the
    monitoring loss may differ by reduction-fusion ulps only."""
    data, topo, model = setup
    cfg = DFedRWConfig(m_chains=4, k_walk=3, batch_size=32)
    ref, fla, rounds = _run_pair(data, topo, model, cfg)
    for sr, mr, sf, mf in rounds:
        pr = jax.tree_util.tree_leaves(ref.params_pytree(sr))
        pf = jax.tree_util.tree_leaves(fla.params_pytree(sf))
        for a, b in zip(pr, pf):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(mr.train_loss, mf.train_loss, rtol=1e-5)
        assert mr.comm_bits_round == mf.comm_bits_round
        assert mr.comm_bits_busiest_round == mf.comm_bits_busiest_round
        np.testing.assert_allclose(mr.gamma_hat, mf.gamma_hat, rtol=1e-6)


def test_parity_bits8_within_quantization_noise(setup):
    """QDFedRW (bits=8): the engines draw independent stochastic-rounding
    uniforms (the flat engine uses the kernel's counter RNG), so trajectories
    agree only up to quantization noise — bounded by one adaptive grid cell
    per payload — while the deterministic parts (comm accounting, batch and
    walk plans) match exactly. (A fixed QuantConfig.s is covered at the
    payload level in test_kernels_quantize — its unit-range grid noise at
    d~1e5 dominates any trajectory tolerance.)"""
    data, topo, model = setup
    cfg = DFedRWConfig(m_chains=4, k_walk=3, batch_size=32,
                       quant=QuantConfig(bits=8))
    ref, fla, rounds = _run_pair(data, topo, model, cfg)
    for sr, mr, sf, mf in rounds:
        assert mr.comm_bits_round == mf.comm_bits_round
        assert mr.comm_bits_busiest_round == mf.comm_bits_busiest_round
        np.testing.assert_allclose(mr.train_loss, mf.train_loss, atol=5e-3)
        np.testing.assert_allclose(mr.gamma_hat, mf.gamma_hat, atol=5e-3)
        pr = jax.tree_util.tree_leaves(ref.params_pytree(sr))
        pf = jax.tree_util.tree_leaves(fla.params_pytree(sf))
        scale = max(float(jnp.abs(a).max()) for a in pr)
        for a, b in zip(pr, pf):
            diff = float(jnp.abs(a - b).max())
            assert diff < 0.05 * scale + 1e-4, (diff, scale)


def test_parity_chain_mode(setup):
    """Chain mode (§VI-F): persisted chain starts and padded fixed-shape
    aggregation plans agree between engines."""
    data, topo, model = setup
    cfg = DFedRWConfig(m_chains=3, k_walk=3, batch_size=32, chain_mode=True)
    ref, fla, rounds = _run_pair(data, topo, model, cfg, rounds=2)
    for sr, mr, sf, mf in rounds:
        np.testing.assert_array_equal(sr.chain_starts, sf.chain_starts)
        assert mr.comm_bits_round == mf.comm_bits_round
        pr = jax.tree_util.tree_leaves(ref.params_pytree(sr))
        pf = jax.tree_util.tree_leaves(fla.params_pytree(sf))
        for a, b in zip(pr, pf):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_parity_under_stragglers(setup):
    """Variable-length chains (truncate mode) mask identically."""
    data, topo, model = setup
    from repro.core import StragglerModel

    cfg = DFedRWConfig(m_chains=4, k_walk=4, batch_size=32,
                       straggler=StragglerModel(h_percent=50, mode="truncate"))
    ref, fla, rounds = _run_pair(data, topo, model, cfg, rounds=2)
    for sr, mr, sf, mf in rounds:
        pr = jax.tree_util.tree_leaves(ref.params_pytree(sr))
        pf = jax.tree_util.tree_leaves(fla.params_pytree(sf))
        for a, b in zip(pr, pf):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_single_trace_across_rounds(setup):
    """Retrace guard: repeated rounds (including chain mode, whose raw
    aggregation plans vary in size) reuse ONE compiled executable."""
    data, topo, model = setup
    for kwargs in ({}, {"chain_mode": True}, {"quant": QuantConfig(bits=8)}):
        cfg = DFedRWConfig(m_chains=4, k_walk=3, batch_size=32, **kwargs)
        runner = DFedRW(model, data, topo, cfg)
        key = jax.random.PRNGKey(1)
        state = runner.init_state(key)
        for _ in range(4):
            key, sub = jax.random.split(key)
            state, _ = runner.run_round(state, sub)
        assert runner.trace_count == 1, kwargs


# ---------------------------------------------------------------- codec


def test_flatten_round_trip():
    model = make_fnn((17, 5), in_dim=33, out_dim=7)
    spec = make_flat_spec(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    assert spec.d == 33 * 17 + 17 + 17 * 5 + 5 + 5 * 7 + 7
    assert spec.d_pad % LANES == 0
    params = model.init(jax.random.PRNGKey(3))
    stacked = jax.tree_util.tree_map(
        lambda p: jax.random.normal(jax.random.PRNGKey(p.size), (6, *p.shape)),
        params,
    )
    flat = flatten_tree(stacked, spec)
    assert flat.shape == (6, spec.d_pad)
    back = jax.tree_util.tree_leaves(unflatten_tree(flat, spec))
    for a, b in zip(jax.tree_util.tree_leaves(stacked), back):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # per-row leaf ids cover every row, in offset order
    ids = spec.row_leaf_ids()
    assert ids.shape == (spec.rows,)
    assert (np.diff(ids) >= 0).all() and ids[0] == 0 and ids[-1] == spec.n_leaves - 1


# ------------------------------------------------- vectorized scatter


@pytest.mark.parametrize("case", range(60))
def test_scatter_matches_sequential_tie_breaking(case):
    """Property test: the winner election plus the row merge of the
    winners' rows (the kernel, interpreted) reproduce the seed engine's
    sequential semantics exactly — later writers win, inactive writers
    never write — across random collision patterns (several chains visiting
    the same device in one step, all-inactive, heavy duplication)."""
    rng = np.random.default_rng(case)
    n = int(rng.integers(2, 13))
    m = int(rng.integers(1, 17))
    buf = rng.normal(size=(n, LANES)).astype(np.float32)
    # small n forces heavy index collisions in most cases
    idx = rng.integers(0, n, size=m).astype(np.int32)
    mask = rng.random(m) < 0.6
    vals = rng.normal(size=(m, LANES)).astype(np.float32)

    expect = buf.copy()
    for c in range(m):
        if mask[c]:
            expect[idx[c]] = vals[c]

    _, wins = elect_writers(jnp.asarray(idx), jnp.asarray(mask), n)
    out = merge_rows(jnp.asarray(buf), jnp.where(wins, jnp.asarray(idx), n),
                     jnp.asarray(vals))
    np.testing.assert_array_equal(np.asarray(out), expect)


# ------------------------------------------------------------ gamma-hat


def test_gamma_hat_excludes_dead_chains():
    """A fully-masked chain's g_last/g0 ratio is garbage (its gradients were
    computed pre-masking) and must not bias the Lemma-1 estimate."""
    grad_sq = jnp.array([[1.0, 400.0], [4.0, 400.0], [9.0, 400.0]])  # (K=3, M=2)
    mask_alive = jnp.array([[True, True, True], [False, False, False]])
    got = float(gamma_hat_from_traj(grad_sq, mask_alive))
    np.testing.assert_allclose(got, 3.0, rtol=1e-4)  # sqrt(9)/sqrt(1) only
    # with both chains alive the (flat) ratio of chain 2 enters the mean
    mask_both = jnp.ones((2, 3), bool)
    got_both = float(gamma_hat_from_traj(grad_sq, mask_both))
    np.testing.assert_allclose(got_both, 2.0, rtol=1e-4)  # mean(3, 1)
