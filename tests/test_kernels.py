"""Per-kernel validation: Pallas (interpret=True) vs the pure-jnp ref.py
oracles, swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import (decode_attention,
                                            decode_attention_ref)
from repro.kernels.flash_attention import (flash_attention,
                                           flash_attention_ref)
from repro.kernels.linear_scan import linear_scan, linear_scan_ref
from repro.kernels.moe_gmm import (moe_gmm, moe_gmm_fused,
                                   moe_gmm_fused_ref, moe_gmm_ref)
from repro.kernels.rwkv_scan import rwkv_scan, rwkv_scan_ref

RNG = np.random.default_rng(0)


def _r(shape, dtype=jnp.float32, scale=1.0):
    return jnp.asarray(RNG.normal(0, scale, shape), dtype)


# --------------------------------------------------------------------- #

@pytest.mark.parametrize("b,t,d,bt,bd", [
    (2, 8, 16, 4, 8), (1, 16, 8, 8, 8), (3, 12, 24, 4, 8), (1, 32, 16, 16, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_linear_scan(b, t, d, bt, bd, dtype):
    a = jnp.asarray(RNG.uniform(0.5, 1.0, (b, t, d)), dtype)
    x = _r((b, t, d), dtype)
    h0 = _r((b, d), dtype)
    y1, h1 = linear_scan_ref(a, x, h0)
    y2, h2 = linear_scan(a, x, h0, force_pallas=True, bt=bt, bd=bd)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), atol=1e-5)


@pytest.mark.parametrize("b,t,h,n,bt", [(2, 8, 2, 8, 4), (1, 16, 3, 16, 8),
                                        (1, 12, 1, 8, 12)])
def test_rwkv_scan(b, t, h, n, bt):
    r, k, v = _r((b, t, h, n)), _r((b, t, h, n)), _r((b, t, h, n))
    w = jnp.asarray(RNG.uniform(0.5, 1.0, (b, t, h, n)), jnp.float32)
    u = _r((h, n))
    s0 = _r((b, h, n, n))
    y1, s1 = rwkv_scan_ref(r, k, v, w, u, s0)
    y2, s2 = rwkv_scan(r, k, v, w, u, s0, force_pallas=True, bt=bt)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-5)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), atol=1e-5)


@pytest.mark.parametrize("b,h,hkv,d,s,bs,win,filled", [
    (2, 4, 2, 16, 32, 8, 0, 20),
    (1, 8, 1, 32, 64, 16, 24, 64),
    (2, 2, 2, 8, 16, 16, 0, 5),
    (1, 4, 4, 64, 32, 8, 8, 30),
])
def test_decode_attention(b, h, hkv, d, s, bs, win, filled):
    q = _r((b, h, d))
    kc, vc = _r((b, s, hkv, d)), _r((b, s, hkv, d))
    pos = np.full((b, s), -1, np.int32)
    pos[:, :filled] = np.arange(filled)
    pos = jnp.asarray(pos)
    qpos = jnp.full((b,), filled - 1, jnp.int32)
    o1 = decode_attention_ref(q, kc, vc, pos, qpos, window=win)
    o2 = decode_attention(q, kc, vc, pos, qpos, window=win,
                          force_pallas=True, bs=bs)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-5)


@pytest.mark.parametrize("b,s,h,hkv,d,bq,bk,win", [
    (2, 32, 4, 2, 16, 8, 8, 0),
    (1, 64, 2, 1, 32, 16, 16, 24),
    (1, 16, 4, 4, 8, 16, 8, 0),
    (2, 32, 8, 2, 16, 8, 16, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, s, h, hkv, d, bq, bk, win, dtype):
    q, k, v = (_r((b, s, h, d), dtype), _r((b, s, hkv, d), dtype),
               _r((b, s, hkv, d), dtype))
    o1 = flash_attention_ref(q, k, v, window=win)
    o2 = flash_attention(q, k, v, window=win, force_pallas=True,
                         bq=bq, bk=bk)
    atol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32), atol=atol)


@pytest.mark.parametrize("e,c,d,f,bc,bd,bf", [
    (4, 16, 32, 24, 8, 16, 8),
    (8, 8, 16, 16, 8, 8, 16),
    (3, 32, 8, 8, 16, 8, 8),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm(e, c, d, f, bc, bd, bf, dtype):
    counts = RNG.integers(0, c + 1, e).astype(np.int32)
    x = RNG.normal(0, 1, (e, c, d)).astype(np.float32)
    for i, n in enumerate(counts):
        x[i, n:] = 0.0  # dead capacity slots hold zeros by construction
    w = RNG.normal(0, 1, (e, d, f)).astype(np.float32)
    x, w = jnp.asarray(x, dtype), jnp.asarray(w, dtype)
    cj = jnp.asarray(counts)
    y1 = moe_gmm_ref(x, w, cj)
    y2 = moe_gmm(x, w, cj, force_pallas=True, bc=bc, bd=bd, bf=bf)
    atol = 1e-4 if dtype == jnp.float32 else 0.25
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y2, np.float32), atol=atol)


def test_moe_gmm_dead_experts_exact_zero():
    """Tiles of experts with zero tokens must be exactly zero (the kernel
    skips their MXU work)."""
    e, c, d, f = 4, 8, 16, 8
    counts = jnp.asarray([0, 8, 0, 3], jnp.int32)
    x = _r((e, c, d))
    x = x.at[0].set(0).at[2].set(0).at[3, 3:].set(0)
    w = _r((e, d, f))
    y = moe_gmm(x, w, counts, force_pallas=True, bc=8, bd=16, bf=8)
    assert float(jnp.abs(y[0]).max()) == 0.0
    assert float(jnp.abs(y[2]).max()) == 0.0


@pytest.mark.parametrize("e,c,d,f,bc,bd,bf", [
    (3, 10, 12, 20, 8, 8, 16),   # nothing divides: every axis padded
    (4, 7, 16, 8, 8, 16, 8),     # C < bc
    (2, 33, 8, 24, 16, 8, 16),   # C just over a tile boundary
])
def test_moe_gmm_non_divisible(e, c, d, f, bc, bd, bf):
    """Regression for the former hard divisibility assert: the kernel now
    pads C/d/F internally and slices the result back."""
    counts = jnp.asarray(RNG.integers(0, c + 1, e), jnp.int32)
    x = RNG.normal(0, 1, (e, c, d)).astype(np.float32)
    for i, n in enumerate(np.asarray(counts)):
        x[i, n:] = 0.0
    x = jnp.asarray(x)
    w = _r((e, d, f))
    y1 = moe_gmm_ref(x, w, counts)
    y2 = moe_gmm(x, w, counts, force_pallas=True, bc=bc, bd=bd, bf=bf)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=1e-4)


# --------------------------------------------------------------------- #
# fused packed-union kernel (docs/kernels.md): interpret-mode Pallas vs
# the jnp oracle vs the dense einsum chain the packed dispatch replaces
# --------------------------------------------------------------------- #

def _fused_inputs(u, c, d, f, activation, full=False):
    counts = (np.full(u, c, np.int32) if full
              else RNG.integers(0, c + 1, u).astype(np.int32))
    x = RNG.normal(0, 1, (u, c, d)).astype(np.float32)
    for i, n in enumerate(counts):
        x[i, n:] = 0.0
    wg = _r((u, d, f)) if activation == "swiglu" else None
    wu, wd = _r((u, d, f)), _r((u, f, d))
    return jnp.asarray(x), wg, wu, wd, jnp.asarray(counts)


def _dense_chain(x, wg, wu, wd, counts, activation):
    """The stacked-einsum FFN the packed dispatch path inlines — the
    bit-level oracle `apply_moe(packed=True)` must match."""
    up = jnp.einsum("ucd,udf->ucf", x, wu,
                    preferred_element_type=jnp.float32)
    if activation == "swiglu":
        g = jnp.einsum("ucd,udf->ucf", x, wg,
                       preferred_element_type=jnp.float32)
        h = jax.nn.silu(g) * up
    else:
        h = jax.nn.gelu(up)
    y = jnp.einsum("ucf,ufd->ucd", h, wd,
                   preferred_element_type=jnp.float32)
    mask = (jnp.arange(x.shape[1])[None, :] < counts[:, None])
    return (y * mask[:, :, None]).astype(x.dtype)


@pytest.mark.parametrize("u,c,d,f,bc,bf", [
    (1, 8, 16, 16, 8, 8),        # U=1 corner (single activated expert)
    (4, 16, 32, 24, 8, 8),
    (8, 8, 16, 16, 8, 16),       # U=E-shaped full union
    (3, 10, 12, 20, 8, 16),      # non-divisible C and F
    (5, 7, 8, 8, 8, 8),
])
@pytest.mark.parametrize("activation", ["swiglu", "gelu"])
def test_moe_gmm_fused_parity(u, c, d, f, bc, bf, activation):
    x, wg, wu, wd, counts = _fused_inputs(u, c, d, f, activation)
    y_ref = moe_gmm_fused_ref(x, wg, wu, wd, counts, activation=activation)
    y_dense = _dense_chain(x, wg, wu, wd, counts, activation)
    y_k = moe_gmm_fused(x, wg, wu, wd, counts, activation=activation,
                        backend="interpret", bc=bc, bf=bf)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_dense),
                               atol=2e-5)
    # the kernel sums its f32 products tile by tile, the oracle in one
    # contraction: reordering a sum of n f32 terms moves it by up to
    # ~n*eps of its magnitude, so outputs in the hundreds need a relative
    # bound beside the absolute one (1e-6 is ~8 f32 ulps)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_k),
                               atol=1e-4, rtol=1e-6)


def test_moe_gmm_fused_full_union_parity():
    """U = E corner with every slot saturated: no masking in play, pure
    fused-matmul parity."""
    x, wg, wu, wd, counts = _fused_inputs(6, 8, 16, 16, "swiglu", full=True)
    y_ref = moe_gmm_fused_ref(x, wg, wu, wd, counts)
    y_k = moe_gmm_fused(x, wg, wu, wd, counts, backend="interpret",
                        bc=8, bf=8)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_k),
                               atol=1e-4)


def test_moe_gmm_fused_dead_slots_exact_zero():
    """Padded union slots (counts == 0) must come out exactly zero — the
    kernel's scalar-prefetch steering never initializes them with real
    expert traffic."""
    x, wg, wu, wd, _ = _fused_inputs(4, 8, 16, 8, "swiglu", full=True)
    counts = jnp.asarray([0, 8, 0, 3], jnp.int32)
    x = x.at[0].set(0).at[2].set(0).at[3, 3:].set(0)
    y = moe_gmm_fused(x, wg, wu, wd, counts, backend="interpret",
                      bc=8, bf=8)
    assert float(jnp.abs(y[0]).max()) == 0.0
    assert float(jnp.abs(y[2]).max()) == 0.0
    assert float(jnp.abs(y[1]).max()) > 0.0


def test_moe_gmm_backend_dispatch():
    """Explicit backend selection: 'ref' and 'interpret' agree; unknown
    backends and unknown tile kwargs are rejected loudly."""
    x, wg, wu, wd, counts = _fused_inputs(2, 8, 8, 8, "swiglu")
    y_ref = moe_gmm_fused(x, wg, wu, wd, counts, backend="ref")
    y_int = moe_gmm_fused(x, wg, wu, wd, counts, backend="interpret")
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_int),
                               atol=1e-4)
    # force_pallas=True off-TPU lowers to interpret mode (the legacy knob)
    y_fp = moe_gmm_fused(x, wg, wu, wd, counts, force_pallas=True)
    np.testing.assert_allclose(np.asarray(y_int), np.asarray(y_fp),
                               atol=1e-6)
    with pytest.raises(ValueError):
        moe_gmm_fused(x, wg, wu, wd, counts, backend="cuda")
    with pytest.raises(TypeError):
        moe_gmm_fused(x, wg, wu, wd, counts, backend="ref", bd=64)
    with pytest.raises(ValueError):
        moe_gmm(x[:, :, :8], wu[:, :8, :], counts, backend="rocm")


@pytest.mark.parametrize("seed", range(4))
def test_moe_gmm_fused_randomized(seed):
    """Randomized U/C/d/F shapes (odd sizes on every axis) against the
    oracle — the fuzz net for the internal-padding logic."""
    rng = np.random.default_rng(seed)
    u = int(rng.integers(1, 7))
    c = int(rng.integers(1, 20))
    d = int(rng.integers(4, 24))
    f = int(rng.integers(4, 24))
    activation = ["swiglu", "gelu"][seed % 2]
    x, wg, wu, wd, counts = _fused_inputs(u, c, d, f, activation)
    y_ref = moe_gmm_fused_ref(x, wg, wu, wd, counts, activation=activation)
    y_k = moe_gmm_fused(x, wg, wu, wd, counts, activation=activation,
                        backend="interpret", bc=8, bf=8)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_k),
                               atol=1e-4)


# --------------------------------------------------------------------- #
# chunked WKV (§Perf 'chunked-wkv') vs serial oracle
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("b,t,h,n,c", [(2, 64, 2, 8, 32), (1, 96, 3, 16, 32),
                                       (2, 32, 1, 8, 8)])
def test_wkv_chunked_matches_serial(b, t, h, n, c):
    from repro.models.rwkv import wkv_chunked, wkv_scan
    r, k, v = _r((b, t, h, n)), _r((b, t, h, n)), _r((b, t, h, n))
    w = jnp.asarray(RNG.uniform(0.3, 0.999, (b, t, h, n)), jnp.float32)
    u = _r((h, n))
    s0 = _r((b, h, n, n))
    y1, states = wkv_scan(r, k, v, w, u, s0)
    y2, s2 = wkv_chunked(r, k, v, w, u, s0, chunk=c)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), atol=2e-4)
    np.testing.assert_allclose(np.asarray(states[-1]), np.asarray(s2),
                               atol=2e-4)
