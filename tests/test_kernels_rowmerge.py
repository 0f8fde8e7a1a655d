"""Row-merge kernel (interpret mode on CPU) against the two ``.at[].set`` row
scatters it replaces in the flat round program: bit for bit, on the write
patterns a round produces."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flatten import elect_writers
from repro.kernels.rowmerge import merge_rows
from repro.kernels.rowmerge.ref import merge_rows_ref

D = 3 * 128


def _round(n, k, m, a, seed, *, active=1.0, agg_hits_winner=False, pad_agg=0, span=None):
    """A round's writes: a (K, M) walk with ties, its winners, and A
    aggregators (the last ``pad_agg`` of them padded ids >= n), all in rows
    below ``span`` if given."""
    rng = np.random.default_rng(seed)
    devs = rng.integers(0, min(n, max(2, (k * m) // 2)), size=(k, m))   # forces ties
    mask = rng.random((k, m)) < active
    _, wins = elect_writers(jnp.asarray(devs.reshape(-1)), jnp.asarray(mask.reshape(-1)), n)
    targets = jnp.where(wins, jnp.asarray(devs.reshape(-1)), n).reshape(k, m)
    real = a - pad_agg
    if agg_hits_winner:
        won = np.unique(np.asarray(targets)[np.asarray(targets) < n])
        rest = np.setdiff1d(np.arange(n), won)
        agg = np.concatenate([won, rng.permutation(rest)])[:real]
    else:
        agg = rng.choice(span or n, size=real, replace=False)
    agg = np.concatenate([agg, n + np.arange(pad_agg)]).astype(np.int32)
    mat = rng.standard_normal((n, D)).astype(np.float32)
    traj = rng.standard_normal((k, m, D)).astype(np.float32)
    upd = rng.standard_normal((a, D)).astype(np.float32)
    return mat, targets, traj, jnp.asarray(agg), upd


def _quant_merge(mat, targets, traj, agg, upd):
    want = merge_rows_ref(jnp.asarray(mat), targets, jnp.asarray(traj), agg, jnp.asarray(upd))
    got = jax.jit(merge_rows, donate_argnums=0)(jnp.asarray(mat), targets, jnp.asarray(traj),
                                                agg, jnp.asarray(upd))
    return got, want


def _fp32_merges(mat, targets, traj, agg, upd):
    """The 32-bit path: the winners' merge, then the aggregators' averages
    of rows read from the merged matrix."""
    n = mat.shape[0]
    rows = jnp.asarray(np.random.default_rng(1).integers(0, n, size=(agg.shape[0], 3)))
    w = jnp.full(rows.shape, 1 / 3, jnp.float32)

    def mix(merge, dev):
        last = merge(dev, targets, jnp.asarray(traj))
        avg = jnp.sum(w[..., None] * last[rows], axis=1)
        return merge(last, agg, avg)

    # both under jit, so the averages round alike
    want = jax.jit(lambda dev: mix(merge_rows_ref, dev))(jnp.asarray(mat))
    got = jax.jit(lambda dev: mix(merge_rows, dev), donate_argnums=0)(jnp.asarray(mat))
    return got, want


CASES = {
    "ties_and_losers": (dict(n=16, k=3, m=6, a=4, seed=0), _quant_merge),
    "inactive_writers": (dict(n=16, k=3, m=6, a=4, seed=1, active=0.5), _quant_merge),
    "aggregator_also_winner": (dict(n=24, k=2, m=5, a=4, seed=2, agg_hits_winner=True),
                               _quant_merge),
    "padded_aggregator_ids": (dict(n=16, k=2, m=5, a=6, seed=3, pad_agg=3), _quant_merge),
    "n13": (dict(n=13, k=2, m=5, a=3, seed=4), _quant_merge),
    "n100": (dict(n=100, k=4, m=8, a=25, seed=5), _quant_merge),
    "no_change": (dict(n=20, k=2, m=4, a=2, seed=6, active=0.0, pad_agg=2), _quant_merge),
    # every write lands in group 0 of 5: groups 1 to 4 pass through unchanged
    "untouched_groups": (dict(n=40, k=1, m=2, a=2, seed=8, span=8), _quant_merge),
    "fp32_two_merges": (dict(n=21, k=3, m=4, a=5, seed=7, agg_hits_winner=True, pad_agg=1),
                        _fp32_merges),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_merge_matches_row_scatters(case):
    kw, run = CASES[case]
    mat, targets, traj, agg, upd = _round(**kw)
    got, want = run(mat, targets, traj, agg, upd)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    changed = np.any(np.asarray(want) != mat, axis=1)
    if case == "no_change":
        assert not changed.any()
    else:
        assert changed.any()
