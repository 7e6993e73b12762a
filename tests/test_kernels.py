"""Per-kernel shape/dtype sweeps against the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:  # property tests skip below; the rest of the module runs
    HAVE_HYPOTHESIS = False

from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.mamba2_ssd import ops as ssd_ops
from repro.kernels.mamba2_ssd.ref import ssd_reference
from repro.kernels.rwkv6_wkv import ops as wkv_ops
from repro.kernels.rwkv6_wkv.ref import wkv6_scan
from repro.kernels.rsp_shuffle import ops as rs_ops
from repro.kernels.rsp_shuffle.ref import rsp_shuffle_ref
from repro.kernels.block_sketch import block_sketch_ref, merge_sketches


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize(
    "B,H,Hkv,S,D,bq,bk",
    [
        (1, 4, 2, 64, 16, 16, 16),
        (2, 2, 2, 32, 32, 8, 16),   # MHA, uneven blocks
        (1, 8, 1, 48, 8, 16, 16),   # MQA, S not power of two
        (1, 2, 2, 128, 64, 128, 128),  # single block pair
    ],
)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_sweep(dtype, B, H, Hkv, S, D, bq, bk, causal):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, Hkv, S, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, Hkv, S, D)).astype(dtype)
    got = fa_ops.flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk)
    want = flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), rtol=tol, atol=tol
    )


def test_flash_grouped_input_layout():
    """The model-native [B, Hkv, G, S, D] layout round-trips correctly."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    B, Hkv, G, S, D = 1, 2, 3, 32, 16
    q = jax.random.normal(ks[0], (B, Hkv, G, S, D))
    k = jax.random.normal(ks[1], (B, Hkv, S, D))
    v = jax.random.normal(ks[2], (B, Hkv, S, D))
    got = fa_ops.flash_attention(q, k, v, causal=True, block_q=8, block_k=8)
    assert got.shape == (B, Hkv, G, S, D)
    want = flash_attention_ref(q.reshape(B, Hkv * G, S, D), k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(got.reshape(B, Hkv * G, S, D)), np.asarray(want), rtol=2e-5, atol=2e-5
    )


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        s_pow=st.integers(4, 7),
        d=st.sampled_from([8, 16, 32]),
        hkv=st.integers(1, 4),
        g=st.integers(1, 4),
        causal=st.booleans(),
        seed=st.integers(0, 1000),
    )
    def test_flash_property(s_pow, d, hkv, g, causal, seed):
        S = 2**s_pow
        ks = jax.random.split(jax.random.PRNGKey(seed), 3)
        q = jax.random.normal(ks[0], (1, hkv * g, S, d))
        k = jax.random.normal(ks[1], (1, hkv, S, d))
        v = jax.random.normal(ks[2], (1, hkv, S, d))
        got = fa_ops.flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
        want = flash_attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=3e-5, atol=3e-5)

else:

    def test_flash_property():
        pytest.importorskip("hypothesis")


# ---------------------------------------------------------------------------
# mamba2 ssd
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "B,L,H,P,N,chunk,ht",
    [
        (1, 32, 2, 8, 4, 8, 2),
        (2, 64, 4, 16, 16, 16, 4),
        (1, 24, 2, 8, 8, 16, 1),   # L padded to chunk multiple
        (1, 16, 8, 4, 4, 16, 8),
    ],
)
def test_ssd_sweep(B, L, H, P, N, chunk, ht):
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    xbar = jax.random.normal(ks[0], (B, L, H, P))
    dA = -jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    Bm = jax.random.normal(ks[2], (B, L, N))
    Cm = jax.random.normal(ks[3], (B, L, N))
    y1, h1 = ssd_ops.ssd(xbar, dA, Bm, Cm, chunk=chunk, head_tile=ht)
    y2, h2 = ssd_reference(xbar, dA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=2e-4, atol=2e-4)


def test_ssd_strong_decay_stable():
    """Very strong decay (dA << 0) must not produce inf/nan (the unstable
    factorization would)."""
    B, L, H, P, N = 1, 64, 2, 4, 4
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    xbar = jax.random.normal(ks[0], (B, L, H, P))
    dA = jnp.full((B, L, H), -30.0)
    Bm = jax.random.normal(ks[1], (B, L, N))
    Cm = jax.random.normal(ks[2], (B, L, N))
    y, h = ssd_ops.ssd(xbar, dA, Bm, Cm, chunk=16, head_tile=2)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(h).all())
    y2, h2 = ssd_reference(xbar, dA, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# rwkv6 wkv
# ---------------------------------------------------------------------------

@pytest.mark.parametrize(
    "B,T,H,C,chunk",
    [(1, 32, 2, 8, 8), (2, 64, 1, 16, 16), (1, 20, 2, 8, 16), (1, 16, 4, 4, 4)],
)
def test_wkv_sweep(B, T, H, C, chunk):
    ks = jax.random.split(jax.random.PRNGKey(4), 4)
    r = jax.random.normal(ks[0], (B, T, H, C))
    k = jax.random.normal(ks[1], (B, T, H, C))
    v = jax.random.normal(ks[2], (B, T, H, C))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H, C)))
    u = jnp.linspace(0.1, 0.9, H * C).reshape(H, C)
    y1, h1 = wkv_ops.wkv6(r, k, v, w, u, chunk=chunk)
    y2, h2 = wkv6_scan(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2), rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h1), np.asarray(h2), rtol=2e-4, atol=2e-4)


def test_wkv_strong_decay_stable():
    B, T, H, C = 1, 32, 1, 8
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    r = jax.random.normal(ks[0], (B, T, H, C))
    k = jax.random.normal(ks[1], (B, T, H, C))
    v = jax.random.normal(ks[2], (B, T, H, C))
    w = jnp.full((B, T, H, C), 1e-6)  # near-total forgetting each step
    u = jnp.full((H, C), 0.5)
    y, h = wkv_ops.wkv6(r, k, v, w, u, chunk=8)
    assert bool(jnp.isfinite(y).all()) and bool(jnp.isfinite(h).all())
    y2, h2 = wkv6_scan(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# rsp shuffle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("R,D,T", [(64, 12, 8), (128, 4, 16), (32, 32, 32)])
def test_rsp_shuffle_sweep(dtype, R, D, T):
    if dtype == jnp.int32:
        x = jax.random.randint(jax.random.PRNGKey(6), (R, D), 0, 1000).astype(dtype)
    else:
        x = jax.random.normal(jax.random.PRNGKey(6), (R, D)).astype(dtype)
    tp, ip = rs_ops.make_permutations(jax.random.PRNGKey(7), R // T, T)
    got = rs_ops.rsp_shuffle(x, tp, ip, tile_rows=T)
    want = rsp_shuffle_ref(x, tp, ip, tile_rows=T)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_rsp_randomize_block_is_permutation():
    x = jnp.arange(128 * 3, dtype=jnp.float32).reshape(128, 3)
    out = rs_ops.rsp_randomize_block(x, jax.random.PRNGKey(8), tile_rows=16)
    assert out.shape == x.shape
    # bijection: same multiset of rows, different order
    a = np.sort(np.asarray(out)[:, 0])
    b = np.sort(np.asarray(x)[:, 0])
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(np.asarray(out), np.asarray(x))


if HAVE_HYPOTHESIS:

    @settings(max_examples=10, deadline=None)
    @given(
        tiles=st.integers(2, 8),
        t_rows=st.sampled_from([4, 8, 16]),
        d=st.integers(1, 8),
        seed=st.integers(0, 10_000),
    )
    def test_rsp_shuffle_property(tiles, t_rows, d, seed):
        R = tiles * t_rows
        x = jax.random.normal(jax.random.PRNGKey(seed), (R, d))
        tp, ip = rs_ops.make_permutations(jax.random.PRNGKey(seed + 1), tiles, t_rows)
        got = rs_ops.rsp_shuffle(x, tp, ip, tile_rows=t_rows)
        want = rsp_shuffle_ref(x, tp, ip, tile_rows=t_rows)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

else:

    def test_rsp_shuffle_property():
        pytest.importorskip("hypothesis")


# ---------------------------------------------------------------------------
# block sketch container: the oracle's Chan merge
# ---------------------------------------------------------------------------

def test_block_sketch_merge_matches_whole():
    rng = np.random.default_rng(15)
    x = rng.normal(size=(700, 4))
    a = block_sketch_ref(x[:300], bins=16, lo=-4, hi=4)
    b = block_sketch_ref(x[300:], bins=16, lo=-4, hi=4)
    m = merge_sketches(a, b)
    whole = block_sketch_ref(x, bins=16, lo=-4, hi=4)
    np.testing.assert_allclose(m.mean, whole.mean, rtol=1e-12)
    np.testing.assert_allclose(m.m2, whole.m2, rtol=1e-9)
    np.testing.assert_array_equal(m.hist, whole.hist)
