"""Per-kernel correctness: Pallas (interpret mode) vs pure-jnp oracle.

Sweeps shapes (aligned + ragged) and all PIM dtypes; int paths must be
bit-exact (integer MACs), fp paths allclose.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.pim_gemm import pim_gemm_fp, pim_gemm_int
from repro.kernels.pim_gemv import pim_gemv_fp, pim_gemv_int
from repro.pimkernel.tileconfig import ALL_DTYPES, PimDType

BLOCK = (128, 256)
SHAPES = [(128, 256), (256, 512), (384, 640), (130, 258), (64, 1024)]


def _rand_int(rng, shape, bits):
    m = 2 ** (bits - 1) - 1
    return rng.integers(-m - 1, m + 1, size=shape)


@pytest.mark.parametrize("h,w", SHAPES)
@pytest.mark.parametrize("w_bits", [8, 4])
@pytest.mark.parametrize("a_bits", [8, 16])
def test_gemv_int_matches_ref(h, w, w_bits, a_bits):
    rng = np.random.default_rng(h * 1000 + w + w_bits + a_bits)
    wq = _rand_int(rng, (h, w), w_bits).astype(np.int8)
    xq = _rand_int(rng, (w,), a_bits)
    xq = xq.astype(np.int8 if a_bits == 8 else np.int16)
    ws = rng.uniform(0.5, 2.0, size=(h,)).astype(np.float32)
    xs = np.float32(0.03)
    wk = ref.pack_w4(wq) if w_bits == 4 else jnp.asarray(wq)
    got = pim_gemv_int(wk, jnp.asarray(xq), jnp.asarray(ws), xs,
                       w_bits=w_bits, block=BLOCK, interpret=True)
    want = ref.ref_gemv_int(wk, xq, ws, xs, w_bits=w_bits)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("b", [1, 4, 9])
@pytest.mark.parametrize("w_bits", [8, 4])
def test_gemm_int_matches_ref(b, w_bits):
    h, w = 192, 384
    rng = np.random.default_rng(b * 7 + w_bits)
    wq = _rand_int(rng, (h, w), w_bits).astype(np.int8)
    xq = _rand_int(rng, (b, w), 8).astype(np.int8)
    ws = rng.uniform(0.5, 2.0, size=(h,)).astype(np.float32)
    xs = np.float32(0.02)
    wk = ref.pack_w4(wq) if w_bits == 4 else jnp.asarray(wq)
    got = pim_gemm_int(wk, jnp.asarray(xq), jnp.asarray(ws), xs,
                       w_bits=w_bits, block=(8, 128, 256), interpret=True)
    want = ref.ref_gemm_int(wk, xq, ws, xs, w_bits=w_bits)
    np.testing.assert_allclose(got, want, rtol=1e-6)


@pytest.mark.parametrize("h,w", [(128, 256), (130, 300)])
def test_gemv_fp_matches_ref(h, w):
    rng = np.random.default_rng(h + w)
    wf = (rng.standard_normal((h, w)) * 0.5).astype(np.float32)
    x = (rng.standard_normal((w,)) * 0.5).astype(np.float32)
    w8 = jnp.asarray(wf).astype(jnp.float8_e4m3fn)
    xb = jnp.asarray(x).astype(jnp.bfloat16)
    got = pim_gemv_fp(w8, xb, block=BLOCK, interpret=True)
    want = ref.ref_gemv_fp(w8, xb)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


def test_gemm_fp_matches_ref():
    rng = np.random.default_rng(3)
    wf = (rng.standard_normal((192, 384)) * 0.5).astype(np.float32)
    xb = (rng.standard_normal((5, 384)) * 0.5).astype(np.float32)
    w8 = jnp.asarray(wf).astype(jnp.float8_e4m3fn)
    xk = jnp.asarray(xb).astype(jnp.bfloat16)
    got = pim_gemm_fp(w8, xk, block=(8, 128, 256), interpret=True)
    want = ref.ref_gemm_fp(w8, xk)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("batched", [False, True])
def test_interpret_none_follows_platform(batched):
    """``interpret=None`` (the default) is ``ops.default_interpret()``:
    the interpreter here, native compilation on TPU — same numbers as an
    explicit ``interpret=True`` off TPU."""
    assert ops.default_interpret() == (jax.default_backend() != "tpu")
    rng = np.random.default_rng(5)
    w8 = jnp.asarray(rng.standard_normal((128, 256)) * 0.5,
                     jnp.float32).astype(jnp.float8_e4m3fn)
    x = jnp.asarray(rng.standard_normal((3, 256)) * 0.5, jnp.bfloat16)
    if batched:
        got = pim_gemm_fp(w8, x, block=(8, 128, 256))
        want = pim_gemm_fp(w8, x, block=(8, 128, 256), interpret=True)
    else:
        got = pim_gemv_fp(w8, x[0], block=BLOCK)
        want = pim_gemv_fp(w8, x[0], block=BLOCK, interpret=True)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_pack_unpack_roundtrip():
    rng = np.random.default_rng(0)
    q = rng.integers(-8, 8, size=(16, 64)).astype(np.int8)
    assert np.array_equal(np.asarray(ref.unpack_w4(ref.pack_w4(q))), q)


@pytest.mark.parametrize("dtype", ALL_DTYPES, ids=lambda d: d.name)
def test_pim_linear_all_dtypes(dtype):
    """End-to-end layer API: kernel path == oracle path, all 7 dtypes."""
    rng = np.random.default_rng(hash(dtype.name) % 2**31)
    wf = (rng.standard_normal((96, 192)) * 0.3).astype(np.float32)
    x = (rng.standard_normal((3, 192)) * 0.8).astype(np.float32)
    qw = ops.prepare_weights(wf, dtype)
    got = ops.pim_linear(x, qw, block=(128, 128), interpret=True)
    want = ops.pim_linear_ref(x, qw)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-4)


def test_quantization_fidelity():
    """Dequantized GEMV approximates the float GEMV (sanity)."""
    rng = np.random.default_rng(5)
    wf = rng.standard_normal((256, 512)).astype(np.float32) * 0.1
    x = rng.standard_normal((512,)).astype(np.float32)
    qw = ops.prepare_weights(wf, PimDType.W8A8)
    got = ops.pim_linear(x, qw, block=BLOCK, interpret=True)
    want = wf @ x
    err = np.linalg.norm(np.asarray(got) - want) / np.linalg.norm(want)
    assert err < 0.02, err
