"""Pallas kernel validation: shape/dtype sweeps vs pure-jnp oracles,
executed with interpret=True on CPU (per-kernel allclose requirement)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.flash_attention.ops import flash_mha
from repro.kernels.flash_attention.ref import flash_ref
from repro.kernels.sgmv.ops import sgmv_apply, sgmv_tokens
from repro.kernels.sgmv.ref import sgmv_ref


# ------------------------------------------------------------------- SGMV
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("R,D,r,O,N,rb", [
    (16, 64, 8, 32, 3, 8),
    (24, 128, 16, 128, 4, 8),
    (8, 256, 4, 64, 1, 4),
    (32, 512, 32, 256, 6, 16),
    # phi3-medium width, not a power of two: D and O split into 1280-wide
    # tiles (the largest divisor of 5120 within the 2048 target)
    (16, 5120, 16, 2560, 3, 8),
])
def test_sgmv_matches_oracle(R, D, r, O, N, rb, dtype):
    ks = jax.random.split(jax.random.PRNGKey(R + N), 4)
    x = jax.random.normal(ks[0], (R, D), jnp.float32).astype(dtype)
    a = (jax.random.normal(ks[1], (N, D, r), jnp.float32) * 0.1).astype(dtype)
    b = (jax.random.normal(ks[2], (N, r, O), jnp.float32) * 0.1).astype(dtype)
    idx = jax.random.randint(ks[3], (R,), 0, N)
    ref = sgmv_ref(x, a, b, idx, scaling=2.0)
    out = sgmv_apply(x, a, b, idx, row_block=rb, scaling=2.0,
                     use_kernel=True)
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=12, deadline=None)
@given(
    R=st.integers(1, 24),
    N=st.integers(1, 5),
    r=st.sampled_from([4, 8, 16]),
    seed=st.integers(0, 2 ** 16),
)
def test_sgmv_property_random_batches(R, N, r, seed):
    """Property: arbitrary (unsorted, unbalanced) adapter assignments match
    the gather oracle exactly."""
    D, O = 32, 48
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (R, D), jnp.float32)
    a = jax.random.normal(ks[1], (N, D, r), jnp.float32) * 0.2
    b = jax.random.normal(ks[2], (N, r, O), jnp.float32) * 0.2
    idx = jax.random.randint(ks[3], (R,), 0, N)
    out = sgmv_apply(x, a, b, idx, row_block=8, use_kernel=True)
    ref = sgmv_ref(x, a, b, idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


def test_sgmv_tokens_layout():
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(ks[0], (4, 6, 64))
    a = jax.random.normal(ks[1], (3, 64, 8)) * 0.1
    b = jax.random.normal(ks[2], (3, 8, 32)) * 0.1
    idx = jnp.array([0, 2, 1, 0])
    out = sgmv_tokens(x, a, b, idx, use_kernel=True)
    ref = sgmv_ref(x.reshape(24, 64), a, b,
                   jnp.repeat(idx, 6)).reshape(4, 6, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-4, rtol=1e-4)


# -------------------------------------------------------------- flash attn
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,H,K,T,hd,win,blk", [
    (2, 4, 2, 64, 16, None, 16),
    (1, 4, 1, 128, 32, 32, 32),
    (2, 2, 2, 96, 16, None, 32),     # T not a multiple of block (pad path)
    (1, 8, 4, 64, 64, 16, 16),
])
def test_flash_matches_oracle(B, H, K, T, hd, win, blk, dtype):
    ks = jax.random.split(jax.random.PRNGKey(B * T + H), 3)
    q = jax.random.normal(ks[0], (B, T, H, hd), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (B, T, K, hd), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (B, T, K, hd), jnp.float32).astype(dtype)
    ref = flash_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), causal=True,
                    window=win).transpose(0, 2, 1, 3)
    out = flash_mha(q, k, v, causal=True, window=win, q_block=blk,
                    kv_block=blk)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=tol, rtol=tol)


@settings(max_examples=8, deadline=None)
@given(
    T=st.sampled_from([32, 48, 64]),
    H=st.sampled_from([2, 4]),
    win=st.sampled_from([None, 8, 16]),
    seed=st.integers(0, 2 ** 16),
)
def test_flash_property(T, H, win, seed):
    hd, K = 16, 2
    if H % K:
        H = K
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (1, T, H, hd))
    k = jax.random.normal(ks[1], (1, T, K, hd))
    v = jax.random.normal(ks[2], (1, T, K, hd))
    out = flash_mha(q, k, v, causal=True, window=win, q_block=16,
                    kv_block=16)
    ref = flash_ref(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                    v.transpose(0, 2, 1, 3), causal=True,
                    window=win).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=3e-5, rtol=3e-5)
