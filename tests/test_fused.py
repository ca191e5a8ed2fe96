"""Fused retrieval megakernel: equivalence with the 3-dispatch turn.

Three layers of contract, mirroring how the kernel is wired in:

* **op level** (``kernels.ops.fused_turn*`` / ``fused_scan*``):
  interpret-mode Pallas == jnp oracle at adversarial shapes —
  non-tile-multiple nlist/Lmax, k near nprobe*Lmax, empty probed
  lists — for every precision.  Float is exact (integer-valued inputs
  make dot products order-independent); bf16 compares values only.
* **backend level** (``FusedTurn`` plugin on IVF/IVF-PQ): the fused
  f32 path is bit-identical to the classic 3-dispatch ``plain_batch``
  and sessioned ``start``/``step`` — ids, scores and every TurnStats
  counter.  Quantised paths hold a recall floor against the float ids.
* **sharded level**: ``shard_backend`` propagates the plugin into the
  sharded scan and the result stays bit-identical to single-device.

CPU runs use mode="ref"/"interpret"; the kernel path itself is
TPU-target (tpu_only coverage lives in test_kernels.py).
"""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import ivf, pq, toploc
from repro.core.backend import IVFBackend, IVFPQBackend
from repro.distributed import retrieval as R
from repro.kernels import ops

K = 10
PRECISIONS = ("f32", "int8", "bf16")


def _mk_lists(rng, p, lmax, d, n_docs):
    """Ragged integer-valued posting lists; list 0 is always empty."""
    lv = rng.integers(-4, 5, size=(p, lmax, d)).astype(np.float32)
    li = np.full((p, lmax), -1, np.int32)
    sizes = rng.integers(0, lmax + 1, size=p)
    sizes[0] = 0
    nid = 0
    for pi in range(p):
        for l in range(sizes[pi]):
            li[pi, l] = nid % n_docs
            nid += 1
        lv[pi, sizes[pi]:] = 0
    return jnp.asarray(lv), jnp.asarray(li)


def _check(a, b, exact_ids):
    va, ia = a[0], a[1]
    vb, ib = b[0], b[1]
    np.testing.assert_allclose(np.asarray(va), np.asarray(vb), rtol=1e-5)
    if exact_ids:
        np.testing.assert_array_equal(np.asarray(ia), np.asarray(ib))


# ---------------------------------------------------------------- op level

@pytest.mark.parametrize("p,lmax,d,b,nprobe,k",
                         [(6, 10, 16, 3, 3, 4),     # non-tile-multiple
                          (5, 7, 8, 1, 5, 8),       # k > real candidates
                          (9, 16, 32, 4, 2, 4)])
@pytest.mark.parametrize("precision", PRECISIONS)
def test_fused_turn_ivf_interpret_vs_ref(p, lmax, d, b, nprobe, k,
                                         precision):
    rng = np.random.default_rng(p * 100 + lmax)
    q = jnp.asarray(rng.integers(-4, 5, size=(b, d)).astype(np.float32))
    cents = jnp.asarray(rng.integers(-4, 5, size=(p, d))
                        .astype(np.float32))
    lv, li = _mk_lists(rng, p, lmax, d, n_docs=200)
    exact = precision != "bf16"
    rref = ops.fused_turn(q, cents, lv, li, nprobe=nprobe, k=k,
                          precision=precision, mode="ref")
    rint = ops.fused_turn(q, cents, lv, li, nprobe=nprobe, k=k,
                          precision=precision, mode="interpret")
    _check(rint, rref, exact_ids=exact)
    if exact:
        np.testing.assert_array_equal(np.asarray(rint[2]),
                                      np.asarray(rref[2]))
    # the standalone fused list scan agrees on the same probe set
    sref = ops.fused_scan(q, lv, li, rref[2], k, precision=precision,
                          mode="ref")
    sint = ops.fused_scan(q, lv, li, rref[2], k, precision=precision,
                          mode="interpret")
    _check(sint, sref, exact_ids=exact)
    if precision == "f32":
        fin = np.isfinite(np.asarray(sref[0]))
        np.testing.assert_array_equal(np.asarray(sint[2])[fin],
                                      np.asarray(sref[2])[fin])


@pytest.mark.parametrize("p,lmax,d,b,nprobe,k,m,C",
                         [(6, 10, 16, 3, 3, 4, 4, 16),
                          (5, 8, 8, 2, 4, 8, 2, 8)])
@pytest.mark.parametrize("precision", PRECISIONS)
def test_fused_turn_pq_interpret_vs_ref(p, lmax, d, b, nprobe, k, m, C,
                                        precision):
    rng = np.random.default_rng(p * 10 + m)
    n_docs = 64
    q = jnp.asarray(rng.integers(-4, 5, size=(b, d)).astype(np.float32))
    cents = jnp.asarray(rng.integers(-4, 5, size=(p, d))
                        .astype(np.float32))
    codes = jnp.asarray(rng.integers(0, C, size=(p, lmax, m))
                        .astype(np.uint8))
    li = np.full((p, lmax), -1, np.int32)
    sizes = rng.integers(0, lmax + 1, size=p)
    sizes[0] = 0
    nid = 0
    for pi in range(p):
        for l in range(sizes[pi]):
            li[pi, l] = nid % n_docs
            nid += 1
    li = jnp.asarray(li)
    tables = jnp.asarray(rng.integers(-4, 5, size=(b, m, C))
                         .astype(np.float32))
    corpus = jnp.asarray(rng.integers(-4, 5, size=(n_docs, d))
                         .astype(np.float32))
    exact = precision != "bf16"
    rref = ops.fused_turn_pq(q, cents, tables, codes, li, corpus,
                             nprobe=nprobe, k=k, rerank=2 * k,
                             precision=precision, mode="ref")
    rint = ops.fused_turn_pq(q, cents, tables, codes, li, corpus,
                             nprobe=nprobe, k=k, rerank=2 * k,
                             precision=precision, mode="interpret")
    _check(rint, rref, exact_ids=exact)
    for fuse_rerank in (True, False):
        sref = ops.fused_scan_pq(tables, q, codes, li, rref[2], corpus,
                                 k, rerank=2 * k, precision=precision,
                                 fuse_rerank=fuse_rerank, mode="ref")
        sint = ops.fused_scan_pq(tables, q, codes, li, rref[2], corpus,
                                 k, rerank=2 * k, precision=precision,
                                 fuse_rerank=fuse_rerank,
                                 mode="interpret")
        _check(sint, sref, exact_ids=exact)


def test_fused_turn_all_probed_lists_empty():
    """Every probed list empty -> all ids -1, scores -inf, no crash."""
    rng = np.random.default_rng(0)
    p, lmax, d, b = 4, 6, 8, 2
    q = jnp.asarray(rng.normal(size=(b, d)).astype(np.float32))
    cents = jnp.asarray(rng.normal(size=(p, d)).astype(np.float32))
    lv = jnp.zeros((p, lmax, d), jnp.float32)
    li = jnp.full((p, lmax), -1, jnp.int32)
    for mode in ("ref", "interpret"):
        v, i, _ = ops.fused_turn(q, cents, lv, li, nprobe=2, k=4,
                                 mode=mode)
        assert np.all(np.asarray(i) == -1)
        assert np.all(np.isneginf(np.asarray(v)))


# ----------------------------------------------------------- backend level

@pytest.fixture(scope="module")
def fused_setup():
    from repro.data import synthetic as SY
    wl = SY.make_workload(SY.WorkloadConfig(
        n_docs=1200, d=32, n_topics=12, n_conversations=3,
        turns_per_conversation=5, seed=3))
    idx = ivf.build(jnp.asarray(wl.doc_vecs), p=24, iters=4,
                    key=jax.random.PRNGKey(0))
    pqi = pq.build_ivf_pq(idx, jnp.asarray(wl.doc_vecs), m=8, iters=4,
                          key=jax.random.PRNGKey(0))
    q = jnp.asarray(wl.conversations.reshape(-1, 32)[:7])
    return idx, pqi, q


BACKENDS = [("ivf", IVFBackend(h=16, nprobe=4)),
            ("ivf_pq", IVFPQBackend(h=16, nprobe=4, rerank=32))]


def _eq_stats(a, b, ctx):
    for f in toploc.TurnStats._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(a, f)), np.asarray(getattr(b, f)),
            err_msg=f"{ctx}: TurnStats.{f}")


@pytest.mark.parametrize("name,base", BACKENDS)
def test_fused_plain_batch_f32_bit_identical(fused_setup, name, base):
    idx, pqi, q = fused_setup
    index = idx if name == "ivf" else pqi
    v0, i0, st0 = base.plain_batch(index, q, k=K)
    fb = dataclasses.replace(base, fused=toploc.FusedTurn())
    v1, i1, st1 = fb.plain_batch(index, q, k=K)
    np.testing.assert_array_equal(np.asarray(v0), np.asarray(v1))
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))
    _eq_stats(st0, st1, name)


@pytest.mark.parametrize("name,base", BACKENDS)
@pytest.mark.parametrize("precision", ("int8", "bf16"))
def test_fused_plain_batch_quantized_recall(fused_setup, name, base,
                                            precision):
    idx, pqi, q = fused_setup
    index = idx if name == "ivf" else pqi
    _, ri, _ = base.plain_batch(index, q, k=K)
    fb = dataclasses.replace(base,
                             fused=toploc.FusedTurn(precision=precision))
    _, gi, _ = fb.plain_batch(index, q, k=K)
    ri, gi = np.asarray(ri), np.asarray(gi)
    rec = np.mean([len(set(ri[r]) & set(gi[r])) / K
                   for r in range(ri.shape[0])])
    assert rec >= 0.9, (name, precision, rec)


@pytest.mark.parametrize("name,base", BACKENDS)
def test_fused_sessioned_start_step_bit_identical(fused_setup, name,
                                                  base):
    idx, pqi, q = fused_setup
    index = idx if name == "ivf" else pqi
    fb = dataclasses.replace(base, fused=toploc.FusedTurn())
    v0a, i0a, sa, st0a = base.start(index, q[0], k=K)
    v0b, i0b, sb, st0b = fb.start(index, q[0], k=K)
    np.testing.assert_array_equal(np.asarray(v0a), np.asarray(v0b))
    np.testing.assert_array_equal(np.asarray(i0a), np.asarray(i0b))
    _eq_stats(st0a, st0b, name + " start")
    v1a, i1a, _, st1a = base.step(index, sa, q[1], k=K)
    v1b, i1b, _, st1b = fb.step(index, sb, q[1], k=K)
    np.testing.assert_array_equal(np.asarray(v1a), np.asarray(v1b))
    np.testing.assert_array_equal(np.asarray(i1a), np.asarray(i1b))
    _eq_stats(st1a, st1b, name + " step")


@pytest.mark.parametrize("n_docs", [1197, 1200])
def test_fused_pq_kernel_serves_any_corpus_size(fused_setup, n_docs):
    """The fused PQ kernel re-ranks from whole 8-row corpus tiles.
    Placing the index pads its lists to the kernel length and its corpus
    rows to whole tiles once; a corpus of any size then serves through
    the kernel (interpret mode) with the XLA path's ids."""
    from repro.data import synthetic as SY
    from repro.kernels import tiling
    wl = SY.make_workload(SY.WorkloadConfig(
        n_docs=n_docs, d=32, n_topics=12, n_conversations=3,
        turns_per_conversation=5, seed=3))
    x = jnp.asarray(wl.doc_vecs)
    idx = ivf.build(x, p=24, iters=4, key=jax.random.PRNGKey(0))
    pqi = pq.build_ivf_pq(idx, x, m=8, iters=4, key=jax.random.PRNGKey(0))
    q = fused_setup[2]
    base = IVFPQBackend(h=16, nprobe=4, rerank=32)
    fb = dataclasses.replace(
        base, fused=toploc.FusedTurn(mode="interpret"))
    placed = fb.place(pqi)
    assert placed.doc_vecs.shape[0] % 8 == 0
    assert placed.lmax == tiling.list_len(pqi.lmax)
    assert base.place(pqi) is pqi          # the XLA path keeps the build
    _, i0, _ = base.plain_batch(pqi, q, k=K)
    _, i1, _ = fb.plain_batch(placed, q, k=K)
    np.testing.assert_array_equal(np.asarray(i0), np.asarray(i1))


# ----------------------------------------------------------- sharded level

@pytest.mark.parametrize("shards",
                         [s for s in (1, 2, 4) if s <= jax.device_count()])
@pytest.mark.parametrize("name,base", BACKENDS)
def test_fused_sharded_bit_identical(fused_setup, name, base, shards):
    idx, pqi, q = fused_setup
    index = idx if name == "ivf" else pqi
    single = base.plain_batch(index, q, k=K)
    fb = dataclasses.replace(base, fused=toploc.FusedTurn())
    mesh = R.retrieval_mesh(shards)
    sh_b, sh_i = R.shard_backend(mesh, fb, index)
    assert sh_b.scan is not None and sh_b.scan.fused is not None, (
        "shard_backend must propagate the fused plugin into the scan")
    v, i, st = sh_b.plain_batch(sh_i, q, k=K)
    np.testing.assert_array_equal(np.asarray(single[0]), np.asarray(v))
    np.testing.assert_array_equal(np.asarray(single[1]), np.asarray(i))
    _eq_stats(single[2], st, f"sharded {name} s={shards}")


@pytest.mark.parametrize("platform,kind,flops,hbm_bw", [
    ("tpu", "TPU v5 lite", 1.97e14, 8.19e11),   # published v5e peaks
    ("cpu", "cpu", None, None),
    ("tpu", "TPU v99", None, None),             # unknown: no guessed peak
])
def test_device_model_is_keyed_by_device_kind(monkeypatch, platform, kind,
                                              flops, hbm_bw):
    from repro.kernels import autotune

    class Dev:
        pass

    dev = Dev()
    dev.platform, dev.device_kind = platform, kind
    monkeypatch.setattr(autotune.jax, "devices", lambda: [dev])
    if kind == "TPU v99":
        with pytest.raises(ValueError, match="TPU v99"):
            autotune.device_model()
    elif platform == "cpu":
        assert autotune.device_model() is autotune.CPU_MODEL
    else:
        hw = autotune.device_model()
        assert (hw.flops, hw.hbm_bw) == (flops, hbm_bw)
