"""Long-context computational paths == dense references:
blockwise (flash-dataflow) attention, chunked Mamba scan, chunkwise mLSTM,
and hierarchical == global MoE dispatch (dropless)."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke_config
from repro.models import moe as moe_lib
from repro.models.layers import _sdpa_blockwise, _sdpa_dense
from repro.models.ssm import _mamba_scan, _mlstm_chunked


@settings(max_examples=8, deadline=None)
@given(st.integers(30, 300), st.integers(1, 3), st.booleans(),
       st.integers(0, 6))
def test_blockwise_sdpa_matches_dense(T, g, causal, seed):
    B, Hkv, D = 2, 2, 16
    H = Hkv * g
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, Hkv, D))
    v = jax.random.normal(ks[2], (B, T, Hkv, D))
    o1 = _sdpa_dense(q, k, v, causal=causal, q_offset=0)
    o2 = _sdpa_blockwise(q, k, v, causal=causal, q_offset=0, kv_block=64)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


def test_blockwise_sdpa_offset_and_valid():
    B, T, H, D = 2, 200, 4, 8
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (B, T, H, D))
    k = jax.random.normal(ks[1], (B, T, H, D))
    v = jax.random.normal(ks[2], (B, T, H, D))
    o1 = _sdpa_dense(q, k, v, causal=True, q_offset=7, kv_len_valid=150)
    o2 = _sdpa_blockwise(q, k, v, causal=True, q_offset=7, kv_len_valid=150,
                         kv_block=32)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


@settings(max_examples=6, deadline=None)
@given(st.integers(100, 700), st.integers(0, 4))
def test_mamba_chunked_matches_full(s, seed):
    b, di, n = 2, 8, 4
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    u = jax.random.normal(ks[0], (b, s, di)) * 0.1
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, di))) * 0.1
    B = jax.random.normal(ks[2], (b, s, n)) * 0.3
    C = jax.random.normal(ks[3], (b, s, n)) * 0.3
    A = -jnp.exp(jax.random.normal(ks[4], (di, n)) * 0.2)
    D = jnp.ones((di,))
    y1, h1 = _mamba_scan(u, dt, B, C, A, D, chunk=4096)   # single-shot
    y2, h2 = _mamba_scan(u, dt, B, C, A, D, chunk=128)    # chunked
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5)


@settings(max_examples=6, deadline=None)
@given(st.integers(80, 400), st.integers(0, 4))
def test_mlstm_chunked_matches_parallel(S, seed):
    B, H, dh = 2, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (B, S, H, dh)) / math.sqrt(dh)
    k = jax.random.normal(ks[1], (B, S, H, dh)) / math.sqrt(dh)
    v = jax.random.normal(ks[2], (B, S, H, dh))
    ip = jax.random.normal(ks[3], (B, S, H))
    fp = jax.random.normal(ks[4], (B, S, H)) + 2.0
    # parallel reference (the paper's stabilized parallel form)
    lf = jax.nn.log_sigmoid(fp)
    a = jnp.cumsum(lf, 1)
    logD = a[:, :, None, :] - a[:, None, :, :] + ip[:, None, :, :]
    tri = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    logD = jnp.where(tri[None, :, :, None], logD, -jnp.inf)
    mrow = jnp.max(logD, 2, keepdims=True)
    Dm = jnp.exp(logD - mrow)
    sc = jnp.einsum("bthk,bshk->btsh", q, k) * Dm
    norm = jnp.maximum(jnp.abs(sc.sum(2)), jnp.exp(-mrow[:, :, 0, :]))
    h_ref = jnp.einsum("btsh,bshk->bthk", sc, v) / norm[..., None]
    h_ch, _ = _mlstm_chunked(q, k, v, ip, fp, chunk=64)
    np.testing.assert_allclose(np.asarray(h_ref), np.asarray(h_ch),
                               atol=5e-4)


@settings(max_examples=8, deadline=None)
@given(st.integers(1, 4), st.integers(4, 24), st.integers(0, 5))
def test_moe_hierarchical_matches_global(B, S, seed):
    cfg = get_smoke_config("deepseek_v3_671b")   # dropless smoke capacity
    params = moe_lib.init_moe(jax.random.PRNGKey(seed), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1),
                          (B, S, cfg.d_model)) * 0.3
    y1, a1 = moe_lib.moe_apply(params, x, cfg)
    cfg2 = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, dispatch="hierarchical"))
    y2, a2 = moe_lib.moe_apply(params, x, cfg2)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(a1["load"]), np.asarray(a2["load"]))
