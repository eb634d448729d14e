"""Pallas kernels vs pure-jnp oracles — shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.amva import kernel as amva_kernel
from repro.kernels.amva import ref as amva_ref
from repro.kernels.flash_attention import jnp_impl
from repro.kernels.flash_attention import kernel as fa_kernel
from repro.kernels.flash_attention import ref as fa_ref
from repro.kernels.ssd_scan import kernel as ssd_kernel
from repro.kernels.ssd_scan import ref as ssd_ref

KEY = jax.random.key(0)


def _qkv(B, S, H, KV, Dh, dtype):
    ks = jax.random.split(jax.random.fold_in(KEY, S * H + KV), 3)
    q = jax.random.normal(ks[0], (B, S, H, Dh), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, Dh), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, Dh), jnp.float32).astype(dtype)
    return q, k, v


FA_CASES = [
    # B, S, H, KV, Dh, causal, window, block
    (2, 128, 4, 2, 32, True, 0, 64),
    (1, 256, 4, 4, 64, True, 64, 64),
    (2, 128, 8, 1, 16, False, 0, 64),
    (1, 128, 2, 2, 80, True, 0, 64),       # odd head dim (stablelm)
    (1, 256, 6, 6, 64, True, 128, 128),    # whisper-ish heads
]


@pytest.mark.parametrize("case", FA_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_kernel_vs_ref(case, dtype):
    B, S, H, KV, Dh, causal, window, blk = case
    q, k, v = _qkv(B, S, H, KV, Dh, dtype)
    ref = fa_ref.attention(q, k, v, causal=causal, window=window)
    out = fa_kernel.flash_attention_fwd(
        q, k, v, causal=causal, window=window, block_q=blk, block_k=blk)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@pytest.mark.parametrize("case", FA_CASES[:3])
def test_flash_jnp_custom_vjp_grads(case):
    B, S, H, KV, Dh, causal, window, blk = case
    q, k, v = _qkv(B, S, H, KV, Dh, jnp.float32)

    def f_ref(q, k, v):
        return (fa_ref.attention(q, k, v, causal=causal,
                                 window=window) ** 2).sum()

    def f_fa(q, k, v):
        return (jnp_impl.flash_attention(q, k, v, causal, window,
                                         blk, blk) ** 2).sum()

    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g_fa = jax.grad(f_fa, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ref, g_fa):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a),
                                   atol=2e-4, rtol=2e-4)


SSD_CASES = [
    # B, S, H, P, N, chunk
    (2, 64, 3, 16, 16, 16),
    (1, 128, 4, 32, 64, 32),
    (1, 96, 2, 64, 128, 32),
    (2, 64, 5, 16, 32, 64),     # chunk > S/2 -> single chunk after clamp
]


@pytest.mark.parametrize("case", SSD_CASES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_kernel_vs_ref(case, dtype):
    B, S, H, P, N, chunk = case
    ks = jax.random.split(jax.random.fold_in(KEY, S + H + P), 5)
    x = jax.random.normal(ks[0], (B, S, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B_ = jax.random.normal(ks[3], (B, S, N)).astype(dtype)
    C_ = jax.random.normal(ks[4], (B, S, N)).astype(dtype)
    yr, sr = ssd_ref.ssd(x, dt, A, B_, C_, chunk=chunk)
    yk, sk = ssd_kernel.ssd_fwd(x, dt, A, B_, C_, chunk=chunk)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(yk, np.float32),
                               np.asarray(yr, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(sk), np.asarray(sr),
                               atol=tol, rtol=tol)


def _amva_batch(n):
    a = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, n), (n,))) * 1e4
    b = jnp.abs(jax.random.normal(jax.random.fold_in(KEY, n + 1), (n,))) * 1e3
    z = jnp.full((n,), 1e4)
    h = jnp.round(jnp.abs(jax.random.normal(
        jax.random.fold_in(KEY, n + 2), (n,))) * 10 + 1)
    return a, b, z, h


# sizes straddle the (8, 128) tile: sub-tile, exact multiples, ragged tails
@pytest.mark.parametrize("n", [1, 7, 128, 1000, 1024, 4096, 4097])
def test_amva_kernel_vs_ref(n):
    a, b, z, h = _amva_batch(n)
    ref = amva_ref.ps_fixed_point(a, b, z, h)
    out = amva_kernel.amva_fwd(a, b, z, h)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


@pytest.mark.parametrize("n", [5, 300, 1024])
@pytest.mark.parametrize("h_users", [1, 4, 25])
def test_mva_kernel_vs_ref(n, h_users):
    a, _, z, _ = _amva_batch(n)
    d = a * 1e-3 + 1.0
    ref = amva_ref.mva_response(d, z, h_users)
    out = amva_kernel.mva_fwd(d, z, h_users=h_users)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_amva_ops_jit_wrappers():
    from repro.kernels.amva import ops as amva_ops
    a, b, z, h = _amva_batch(200)
    np.testing.assert_array_equal(
        np.asarray(amva_ops.ps_fixed_point(a, b, z, h)),
        np.asarray(amva_ref.ps_fixed_point(a, b, z, h)))
    np.testing.assert_array_equal(
        np.asarray(amva_ops.mva_response(a * 1e-3 + 1.0, z, 8)),
        np.asarray(amva_ref.mva_response(a * 1e-3 + 1.0, z, 8)))


def test_amva_fixed_point_converges_monotonically():
    """The PS iteration T <- a*max(1, hT/(T+z)) + b starts at T0 = a + b,
    a lower bound of the fixed point, and the map is increasing — so the
    kernel's iterates must be nondecreasing in the iteration count and the
    residual must shrink to nothing at the production iteration budget."""
    a, b, z, h = _amva_batch(512)
    budget = amva_kernel.PS_ITERS
    ts = [np.asarray(amva_kernel.amva_fwd(a, b, z, h, iters=k))
          for k in (1, 2, 5, 10, 20, budget, 2 * budget)]
    for lo, hi in zip(ts, ts[1:]):
        # slack = a few f32 ulps at the iterate's own scale
        assert (hi >= lo - 1e-5 * np.abs(lo) - 1e-3).all()
    r_early = np.abs(ts[2] - ts[1])             # residual over iters 2..5
    r_late = np.abs(ts[5] - ts[4])              # residual over 20..budget
    assert (r_late <= r_early + 1e-5 * np.abs(ts[5]) + 1e-3).all()
    rel = np.abs(ts[6] - ts[5]) / np.maximum(np.abs(ts[6]), 1e-9)
    assert rel.max() < 1e-4                     # converged at the budget
