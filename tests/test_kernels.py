"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

Per the deliverable: every kernel is swept over shapes and dtypes and
asserted allclose against its ref.py oracle.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels import ops, ref

# Kernel shape/dtype sweeps dominate suite wall clock; CI runs them in the
# slow tier (see README "Test tiers").
pytestmark = pytest.mark.slow


def _mk_qkv(key, B, S, H, KV, hd, dtype):
    ks = jax.random.split(key, 3)
    q = jax.random.normal(ks[0], (B, S, H, hd)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, KV, hd)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, KV, hd)).astype(dtype)
    return q, k, v


def _ref_bshd(q, k, v, **kw):
    t = lambda x: x.transpose(0, 2, 1, 3)
    return t(ref.flash_attention(t(q), t(k), t(v), **kw))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2e-2)])
@pytest.mark.parametrize("B,S,H,KV,hd", [
    (1, 128, 1, 1, 64),      # minimal
    (2, 256, 4, 2, 64),      # GQA rep=2
    (1, 384, 8, 1, 128),     # MQA, unaligned S (384=3x128)
    (1, 130, 4, 4, 64),      # padding path (S not multiple of block)
])
def test_flash_attention_shapes_dtypes(B, S, H, KV, hd, dtype, tol):
    q, k, v = _mk_qkv(jax.random.PRNGKey(0), B, S, H, KV, hd, dtype)
    out = ops.flash_attention(q, k, v, causal=True)
    want = _ref_bshd(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("window", [32, 128, None])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_masks(window, causal):
    q, k, v = _mk_qkv(jax.random.PRNGKey(1), 2, 256, 4, 2, 64, jnp.float32)
    out = ops.flash_attention(q, k, v, causal=causal, window=window)
    want = _ref_bshd(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=2e-5)


@given(S=st.integers(2, 12), V=st.integers(3, 150))
@settings(max_examples=12, deadline=None)
def test_chain_propagate_sweep(S, V):
    key = jax.random.PRNGKey(S * 1000 + V)
    ks = jax.random.split(key, 3)
    M = jax.random.uniform(ks[0], (S, V, V)) * 0.2
    src = jax.random.uniform(ks[1], (S, V))
    t = jax.random.uniform(ks[2], (S, V))
    np.testing.assert_allclose(
        np.asarray(ops.propagate_step(t, M, src)),
        np.asarray(ref.propagate_step(t, M, src)), atol=1e-5, rtol=1e-5)


def test_chain_fixed_point_matches_traffic_solver():
    """The kernel's Neumann fixed point equals the dense linear solve used
    by core.traffic — i.e. the kernel really is the paper's hot loop."""
    from repro.core import network, gp, traffic
    inst = network.table_ii_instance("abilene", seed=0)
    phi = gp.init_phi(inst)
    fl = traffic.flows(inst, phi)
    A, K1, V = inst.A, inst.K1, inst.V
    # stage 0 of each app: t = Phi^T t + r  ->  row-vector form t = t M + r
    M = phi.e[:, 0]                                  # (A, V, V); M[i,j]=phi_ij
    src = inst.r                                     # (A, V)
    t_kernel = ops.solve_fixed_point(M, src, sweeps=V)
    np.testing.assert_allclose(np.asarray(t_kernel), np.asarray(fl.t[:, 0]),
                               atol=1e-3, rtol=1e-3)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4), (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("Q,H,P,N", [(128, 2, 32, 16), (64, 1, 64, 32), (128, 4, 64, 128)])
def test_ssd_chunk_shapes_dtypes(Q, H, P, N, dtype, tol):
    Bz, nc = 1, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    xh = jax.random.normal(ks[0], (Bz, nc, Q, H, P)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (Bz, nc, Q, H)))
    A = -jnp.exp(0.2 * jax.random.normal(ks[2], (H,)))
    cum = jnp.cumsum(dt * A[None, None, None], axis=2)
    BH = (0.3 * jax.random.normal(ks[3], (Bz, nc, Q, H, N))).astype(dtype)
    CH = (0.3 * jax.random.normal(jax.random.PRNGKey(9), (Bz, nc, Q, H, N))).astype(dtype)
    y, stt = ops.ssd_chunk(xh, dt, None, cum, BH, CH)
    yr, str_ = ref.ssd_chunk(xh, dt, cum, BH, CH)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr), atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(stt), np.asarray(str_), atol=tol, rtol=tol)


def test_ssm_model_path_with_kernel_matches_jnp():
    """models.ssm.ssd_chunked(use_kernel=True) == use_kernel=False."""
    from repro.models import ssm
    B, S, H, P, G, N = 1, 256, 4, 32, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    xh = jax.random.normal(ks[0], (B, S, H, P))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A = -jnp.exp(0.2 * jax.random.normal(ks[2], (H,)))
    Bc = 0.3 * jax.random.normal(ks[3], (B, S, G, N))
    Cc = 0.3 * jax.random.normal(ks[4], (B, S, G, N))
    y0, h0 = ssm.ssd_chunked(xh, dt, A, Bc, Cc, use_kernel=False)
    y1, h1 = ssm.ssd_chunked(xh, dt, A, Bc, Cc, use_kernel=True)
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(h0), np.asarray(h1), atol=2e-4, rtol=2e-4)


def test_attention_model_path_with_kernel_matches_jnp():
    """models.attention.sdpa(use_kernel=True) == pure jnp path."""
    from repro.models import attention
    B, S, H, KV, hd = 1, 256, 4, 2, 64
    q, k, v = _mk_qkv(jax.random.PRNGKey(2), B, S, H, KV, hd, jnp.float32)
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    out0 = attention.sdpa(q, k, v, q_pos=pos, kv_pos=pos, causal=True)
    out1 = attention.sdpa(q, k, v, q_pos=pos, kv_pos=pos, causal=True,
                          use_kernel=True)
    np.testing.assert_allclose(np.asarray(out0), np.asarray(out1), atol=2e-5)
