"""The fused qdq Pallas kernel the round runs, against its plain jax.numpy
oracle (ref.py) in interpret mode (the kernel body executes on CPU): bit
for bit over a shape/dtype/bits sweep, with the fused receiver base, and
through `payload_quantize_dequantize` against a per-leaf oracle; plus the
wire laws of the payload path (one grid cell, fixed interval, counter RNG)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.flatten import make_flat_spec
from repro.kernels.quantize import payload_quantize_dequantize
from repro.kernels.quantize.quantize import LANES, qdq_rows_kernel_call
from repro.kernels.quantize.ref import counter_uniforms, qdq_rows_ref
from repro.models import make_fnn

SHAPES = [(64,), (1000,), (128, 128), (64, 129), (3, 5, 7), (65536,), (2048, 33)]
DTYPES = [jnp.float32, jnp.bfloat16]
BITS = [4, 8]


def _seed(key):
    return jax.random.key_data(key).reshape(-1)[:2]


def _one_tensor_rows(shape, dtype, bits, key):
    """One wire tensor laid out in 128-lane rows as the flat layout pads
    it, rounded through ``dtype``, with its adaptive (s, norm) per row."""
    w = (jax.random.normal(key, shape, jnp.float32) * 2.3).astype(dtype)
    flat = w.reshape(-1).astype(jnp.float32)
    w2d = jnp.pad(flat, (0, (-flat.size) % LANES)).reshape(-1, LANES)
    rows = w2d.shape[0]
    levels = (1 << (bits - 1)) - 1
    norm = jnp.sqrt(jnp.sum(flat * flat))
    s = jnp.max(jnp.abs(flat)) / norm / levels
    return w2d, jnp.full((rows,), s), jnp.full((rows,), norm)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("bits", BITS)
def test_qdq_kernel_matches_oracle(shape, dtype, bits):
    key = jax.random.PRNGKey(hash((shape, bits)) % (2**31))
    w2d, s_rows, norm_rows = _one_tensor_rows(shape, dtype, bits, key)
    got = qdq_rows_kernel_call(w2d, s_rows, norm_rows, bits=bits, seed=_seed(key),
                               interpret=True)
    want = qdq_rows_ref(w2d, s_rows, norm_rows, bits=bits, seed=_seed(key))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # the layout's padding lanes carry zero and stay zero
    np.testing.assert_array_equal(np.asarray(got).reshape(-1)[int(np.prod(shape)):], 0.0)


@pytest.mark.parametrize("bits", BITS)
def test_qdq_kernel_error_bound(bits):
    """Reconstruction error within one grid cell: |deq - w| <= s * ||w||."""
    key = jax.random.PRNGKey(7)
    w2d, s_rows, norm_rows = _one_tensor_rows((4096,), jnp.float32, bits, key)
    w2d = w2d * 10.0
    norm_rows = norm_rows * 10.0
    deq = qdq_rows_kernel_call(w2d, s_rows, norm_rows, bits=bits, seed=_seed(key),
                               interpret=True)
    cell = float(s_rows[0] * norm_rows[0])
    assert float(jnp.abs(deq - w2d).max()) <= cell * (1 + 1e-5)


def test_qdq_kernel_base_fusion_matches_oracle():
    """out = base + Q^-1(Q(w)) at a row count that is NOT a multiple of
    ROW_TILE (the single-block interpret path)."""
    rng = np.random.default_rng(2)
    rows = 37
    w = jnp.asarray(rng.normal(size=(rows, LANES)).astype(np.float32) * 0.1)
    base = jnp.asarray(rng.normal(size=(rows, LANES)).astype(np.float32))
    s_rows = jnp.asarray(rng.uniform(1e-4, 1e-2, rows).astype(np.float32))
    n_rows = jnp.asarray(rng.uniform(0.5, 3.0, rows).astype(np.float32))
    seed = jnp.asarray([12345, 678], jnp.uint32)
    got = qdq_rows_kernel_call(w, s_rows, n_rows, bits=8, seed=seed, base2d=base,
                               interpret=True)
    want = qdq_rows_ref(w, s_rows, n_rows, bits=8, seed=seed, base2d=base)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ------------------------------------------------------------ payload path


def _model_payload(b, seed=0, scale=0.05):
    model = make_fnn((23,), in_dim=17, out_dim=5)
    spec = make_flat_spec(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    rng = np.random.default_rng(seed)
    flat = jnp.asarray(rng.normal(size=(b, spec.d_pad)).astype(np.float32) * scale)
    # zero the padding lanes, as the flat engine guarantees
    mask = np.zeros(spec.d_pad, np.float32)
    for off, size in zip(spec.offsets, spec.sizes):
        mask[off:off + size] = 1.0
    return spec, flat * jnp.asarray(mask)


@pytest.mark.parametrize("per_message", [False, True])
def test_payload_qdq_matches_per_leaf_oracle(per_message):
    """A three-message payload through the fused path equals the paper's
    per-leaf wire round trip (adaptive grid per wire tensor: per message
    and leaf at Eq. 14, per leaf across messages at Eq. 13) fed the
    kernel's replayed counter uniforms."""
    spec, flat = _model_payload(3, seed=4)
    key = jax.random.PRNGKey(42)
    bits, levels = 8, 127
    got = payload_quantize_dequantize(flat, spec, per_message=per_message, bits=bits,
                                      key=key)
    u = counter_uniforms(3 * spec.rows, _seed(key)).reshape(3, spec.d_pad)
    leaves = []
    for off, psize in zip(spec.offsets, spec.padded_sizes):
        w, u_l = flat[:, off:off + psize], u[:, off:off + psize]
        axis = 1 if per_message else None
        norm = jnp.sqrt(jnp.sum(w * w, axis=axis, keepdims=True))
        safe = jnp.where(norm > 0, norm, 1.0)
        xmax = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / safe
        s = jnp.where(xmax > 0, xmax / levels, 1.0)
        x = jnp.abs(w) / safe
        ell = jnp.floor(x / s)
        idx = jnp.clip(ell + (u_l < x / s - ell), 0, levels)
        leaves.append(idx * jnp.sign(w) * s * norm)
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(jnp.concatenate(leaves, axis=1)))


@pytest.mark.parametrize("per_message", [False, True])
@pytest.mark.parametrize("bits", [4, 8])
def test_payload_qdq_error_within_one_cell_and_pad_invariant(per_message, bits):
    """The fused payload pass (counter RNG) keeps every element within one
    adaptive grid cell of its wire tensor and leaves padding lanes zero."""
    spec, flat = _model_payload(4, seed=3)
    out = payload_quantize_dequantize(flat, spec, per_message=per_message,
                                      bits=bits, key=jax.random.PRNGKey(7))
    levels = (1 << (bits - 1)) - 1
    out_np, w_np = np.asarray(out), np.asarray(flat)
    for off, size, psize in zip(spec.offsets, spec.sizes, spec.padded_sizes):
        blk_w = w_np[:, off:off + size]
        blk_o = out_np[:, off:off + size]
        if per_message:
            norm = np.linalg.norm(blk_w, axis=1, keepdims=True)
            cell = np.max(np.abs(blk_w), axis=1, keepdims=True) / levels
        else:
            norm = np.linalg.norm(blk_w)
            cell = np.abs(blk_w).max() / levels
        assert (np.abs(blk_o - blk_w) <= cell * np.ones_like(norm) * (1 + 1e-5)
                + 1e-7).all()
        # padding lanes stay exactly zero
        np.testing.assert_array_equal(out_np[:, off + size:off + psize], 0.0)


def test_payload_qdq_honors_fixed_interval():
    """QuantConfig.s (fixed grid interval) reaches the fused payload path:
    every reconstructed element sits on the s * ||w_seg|| grid and within
    one cell of its input."""
    s = 1.0 / 127
    spec, flat = _model_payload(3, seed=8)
    out = payload_quantize_dequantize(flat, spec, per_message=True, bits=8,
                                      s=s, key=jax.random.PRNGKey(13))
    out_np, w_np = np.asarray(out), np.asarray(flat)
    for off, size in zip(spec.offsets, spec.sizes):
        blk_w = w_np[:, off:off + size]
        blk_o = out_np[:, off:off + size]
        norm = np.linalg.norm(blk_w, axis=1, keepdims=True)
        cell = s * norm
        assert (np.abs(blk_o - blk_w) <= cell * (1 + 1e-5) + 1e-7).all()
        # grid membership: out / (s * norm) is an integer index in [-127, 127]
        idx = blk_o / np.maximum(cell, 1e-12)
        np.testing.assert_allclose(idx, np.round(idx), atol=2e-3)
        assert np.abs(np.round(idx)).max() <= 127


def test_payload_qdq_base_fusion():
    """base + deq fusion equals deq-then-add."""
    spec, flat = _model_payload(2, seed=5)
    base = jnp.ones_like(flat) * 0.25
    key = jax.random.PRNGKey(11)
    plain = payload_quantize_dequantize(flat, spec, per_message=True, bits=8, key=key)
    fused = payload_quantize_dequantize(flat, spec, per_message=True, bits=8,
                                        key=key, base=base)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(base + plain),
                               rtol=1e-6, atol=1e-7)


def test_counter_rng_unbiased_and_key_sensitive():
    """The in-kernel counter-hash uniforms give unbiased stochastic rounding
    (averaged over keys) and decorrelate across keys."""
    spec, flat = _model_payload(1, seed=9, scale=0.1)
    n = 120
    acc = jnp.zeros_like(flat)
    first = None
    for i in range(n):
        o = payload_quantize_dequantize(flat, spec, per_message=False, bits=8,
                                        key=jax.random.PRNGKey(1000 + i))
        if first is None:
            first = o
        acc = acc + o
    assert bool(jnp.any(acc / n != first)), "outputs identical across keys"
    # per-leaf unbiasedness: mean reconstruction within a few SE of w
    w_np = np.asarray(flat)
    mean = np.asarray(acc / n)
    for off, size in zip(spec.offsets, spec.sizes):
        blk_w = w_np[:, off:off + size]
        blk_m = mean[:, off:off + size]
        cell = np.abs(blk_w).max() / 127.0
        assert np.abs(blk_m - blk_w).max() < 6.0 * cell / np.sqrt(n) * np.sqrt(12) + 1e-7
