"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.kernels import ops, ref, sorting, tiling
from repro.kernels import flash_attention as fa


RNG = np.random.default_rng(42)


# ---------------------------------------------------------------- sorting

@pytest.mark.parametrize("shape", [(16,), (4, 32), (2, 2, 64), (256,)])
def test_bitonic_sort(shape):
    v = jnp.asarray(RNG.normal(size=shape).astype(np.float32))
    i = jnp.broadcast_to(jnp.arange(shape[-1], dtype=jnp.int32), shape)
    sv, si = sorting.bitonic_sort_desc(v, i)
    ref_v = -np.sort(-np.asarray(v), axis=-1)
    np.testing.assert_allclose(np.asarray(sv), ref_v)
    gathered = np.take_along_axis(np.asarray(v), np.asarray(si), axis=-1)
    np.testing.assert_allclose(gathered, ref_v)


@pytest.mark.parametrize("k", [4, 16, 64])
def test_bitonic_merge(k):
    a = np.sort(RNG.normal(size=(3, k)).astype(np.float32))[:, ::-1]
    b = np.sort(RNG.normal(size=(3, k)).astype(np.float32))[:, ::-1]
    mv, _ = sorting.merge_topk_desc(
        jnp.asarray(a.copy()), jnp.zeros((3, k), jnp.int32),
        jnp.asarray(b.copy()), jnp.ones((3, k), jnp.int32))
    expect = -np.sort(-np.concatenate([a, b], -1))[:, :k]
    np.testing.assert_allclose(np.asarray(mv), expect)


@pytest.mark.parametrize("shape", [(1,), (8,), (3, 16), (2, 4, 128)])
def test_reverse_lanes_is_flip(shape):
    """The roll-based lane reversal the merges use in place of jnp.flip
    (Mosaic cannot lower lax.rev) moves every element exactly."""
    x = jnp.asarray(RNG.normal(size=shape).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(sorting.reverse_lanes(x)),
                                  np.flip(np.asarray(x), axis=-1))


@pytest.mark.parametrize("shape,to,block_bytes", [
    ((37, 5, 7), 8, 1000),        # blocks of 7 rows, the last overlaps
    ((37, 5, 7), 8, 1 << 20),     # one block
    ((64, 84), 128, 4 * 84 * 8),  # 8 blocks of 8 rows, no overlap
    ((3, 6), 6, 8),               # already at length: returned as is
    ((1, 3, 2), 4, 1),            # one row per block
])
def test_pad_rows_blocked_is_pad(shape, to, block_bytes):
    """The blocked placement pad writes exactly what jnp.pad does."""
    x = jnp.asarray(RNG.normal(size=shape).astype(np.float32))
    got = tiling.pad_rows_blocked(x, to, -1.0, block_bytes=block_bytes)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(tiling.pad_axis(x, 1, to, -1.0)))


# ---------------------------------------------------------- centroid_topk

@pytest.mark.parametrize("b,d,p,k,blk", [
    (1, 32, 256, 8, 64), (8, 64, 512, 16, 128), (4, 128, 1024, 32, 256),
    (3, 48, 1000, 10, 512),      # non-pow2 p/k through ops padding
])
def test_centroid_topk_sweep(b, d, p, k, blk):
    q = jnp.asarray(RNG.normal(size=(b, d)).astype(np.float32))
    c = jnp.asarray(RNG.normal(size=(p, d)).astype(np.float32))
    v, i = ops.centroid_topk(q, c, k, mode="interpret", blk_p=blk)
    rv, ri = ref.centroid_topk(q, c, k)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-4,
                               atol=1e-4)


@pytest.mark.tpu_only
def test_centroid_topk_kernel_mode_smoke():
    """Compile-and-run the real Pallas TPU kernel (mode='kernel', no
    interpreter).  Auto-skipped off-TPU (see conftest/pytest.ini)."""
    q = jnp.asarray(RNG.normal(size=(4, 64)).astype(np.float32))
    c = jnp.asarray(RNG.normal(size=(512, 64)).astype(np.float32))
    v, i = ops.centroid_topk(q, c, 8, mode="kernel")
    rv, ri = ref.centroid_topk(q, c, 8)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_centroid_topk_dtypes(dtype):
    q = jnp.asarray(RNG.normal(size=(4, 64)).astype(np.float32)).astype(dtype)
    c = jnp.asarray(RNG.normal(size=(256, 64)).astype(np.float32)).astype(dtype)
    v, i = ops.centroid_topk(q, c, 8, mode="interpret")
    rv, ri = ref.centroid_topk(q, c, 8)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))


# -------------------------------------------------------------- ivf_scan

@pytest.mark.parametrize("b,d,p,lmax,npb,k", [
    (2, 32, 32, 64, 4, 8), (4, 64, 64, 128, 8, 16),
    (1, 32, 16, 100, 4, 10),     # non-pow2 lmax/k through ops padding
])
def test_ivf_scan_sweep(b, d, p, lmax, npb, k):
    lv = RNG.normal(size=(p, lmax, d)).astype(np.float32)
    li = RNG.integers(0, 100000, (p, lmax)).astype(np.int32)
    pad = RNG.uniform(size=(p, lmax)) < 0.25
    li[pad] = -1
    lv[pad] = 0
    q = jnp.asarray(RNG.normal(size=(b, d)).astype(np.float32))
    sel = jnp.asarray(np.stack(
        [RNG.permutation(p)[:npb] for _ in range(b)]).astype(np.int32))
    v, i = ops.ivf_scan(q, jnp.asarray(lv), jnp.asarray(li), sel, k,
                        mode="interpret")
    rv, ri = ref.ivf_scan_batch(q, jnp.asarray(lv), jnp.asarray(li), sel, k)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ri))
    np.testing.assert_allclose(np.asarray(v), np.asarray(rv), rtol=1e-4,
                               atol=1e-4)


# -------------------------------------------------------- flash attention

@pytest.mark.parametrize("b,h,hkv,s,d,causal", [
    (1, 4, 4, 128, 32, True), (2, 8, 2, 256, 64, True),
    (1, 4, 1, 128, 64, False), (1, 4, 4, 128, 32, False),
])
def test_flash_attention_sweep(b, h, hkv, s, d, causal):
    q = jnp.asarray(RNG.normal(size=(b, h, s, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(np.float32))
    o = fa.flash_attention(q, k, v, causal=causal, blk_q=64, blk_kv=64,
                           interpret=True)
    r = ref.mha_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-3,
                               atol=2e-3)


def test_flash_attention_mla_dims():
    """MLA: value head dim != qk head dim."""
    q = jnp.asarray(RNG.normal(size=(1, 4, 128, 48)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(1, 4, 128, 48)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(1, 4, 128, 32)).astype(np.float32))
    o = fa.flash_attention(q, k, v, causal=True, blk_q=64, blk_kv=64,
                           interpret=True)
    r = ref.mha_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("b,h,hkv,s,d", [
    (2, 8, 2, 512, 64), (1, 4, 4, 256, 32),
])
def test_flash_decode_sweep(b, h, hkv, s, d):
    q = jnp.asarray(RNG.normal(size=(b, h, d)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(b, hkv, s, d)).astype(np.float32))
    clen = jnp.asarray(RNG.integers(1, s, b).astype(np.int32))
    o = fa.flash_decode(q, k, v, clen, blk_kv=128, interpret=True)
    r = ref.decode_attention(q, k, v, clen)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=2e-3,
                               atol=2e-3)


def test_flash_attention_grad_matches_ref():
    q = jnp.asarray(RNG.normal(size=(1, 2, 64, 16)).astype(np.float32))
    k = jnp.asarray(RNG.normal(size=(1, 2, 64, 16)).astype(np.float32))
    v = jnp.asarray(RNG.normal(size=(1, 2, 64, 16)).astype(np.float32))

    def f_op(q, k, v):
        return ops.flash_attention(q, k, v, causal=True, mode="ref").sum()

    def f_ref(q, k, v):
        return ref.mha_attention(q, k, v, causal=True).sum()

    g_op = jax.grad(f_op, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_op, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# -------------------------------------------------------- embedding_bag

@pytest.mark.parametrize("v,d,b,l,weighted,agg", [
    (100, 16, 8, 4, False, "sum"), (500, 32, 16, 10, True, "sum"),
    (100, 16, 8, 4, False, "mean"), (256, 64, 4, 20, True, "mean"),
])
def test_embedding_bag_sweep(v, d, b, l, weighted, agg):
    table = jnp.asarray(RNG.normal(size=(v, d)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(-1, v, (b, l)).astype(np.int32))
    w = (jnp.asarray(RNG.uniform(0.5, 2.0, (b, l)).astype(np.float32))
         if weighted else None)
    o = ops.embedding_bag(table, ids, w, agg=agg, mode="interpret")
    r = ref.embedding_bag(table, ids, w, mode=agg)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=1e-5,
                               atol=1e-5)


def test_embedding_bag_grad():
    table = jnp.asarray(RNG.normal(size=(50, 8)).astype(np.float32))
    ids = jnp.asarray(RNG.integers(0, 50, (4, 3)).astype(np.int32))

    def f(t):
        return (ops.embedding_bag(t, ids, mode="ref") ** 2).sum()

    g = jax.grad(f)(table)
    # only looked-up rows should have gradient
    touched = np.zeros(50, bool)
    touched[np.asarray(ids).reshape(-1)] = True
    gn = np.linalg.norm(np.asarray(g), axis=-1)
    assert np.all(gn[~touched] == 0)
    assert np.all(gn[touched] > 0)
