"""Ahead-of-time compiles for a described (not attached) TPU v5e chip.

The serving path's Pallas kernels and jitted steps are compiled at the
real widths — d = 768, p = 2^14, B = 32, nprobe = 64 — by the TPU
compiler, which runs here without a chip.  A kernel Mosaic refuses
(block tiling, an op it cannot lower), or a program that does not fit
the chip's 16 GiB of HBM, fails here instead of on the chip.

The topology is described inside a fixture, never at import time: only
one process may load the TPU library, and it keeps it until it exits.
"""
import functools

import pytest
import jax
import jax.numpy as jnp

from repro.core import backend as B
from repro.core import ivf, kmeans, pq, toploc
from repro.kernels import ops, tiling
from repro.serving import sessions

HBM_BYTES = 16 * 1024 ** 3

BATCH, D, P, NPROBE, K = 32, 768, 1 << 14, 64, 10
LMAX = 84            # the largest list at capacity factor 1.3
LPAD = 128           # the list length placed for the kernels (list_len)
M, CODES, N_DOCS, H = 48, 256, 1 << 20, 1024

f32, i32, u8 = jnp.float32, jnp.int32, jnp.uint8


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def spec(topo):
    """``spec(shape, dtype)`` → a ShapeDtypeStruct on one v5e chip."""
    from jax.sharding import SingleDeviceSharding
    one = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=one)


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """Entries compiled for a described chip cannot be read back, so the
    persistent compilation cache stays off around these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(fn, *args, **static):
    """Compile ``fn`` (jitted here unless it already is) for the chip
    and check that it fits HBM."""
    jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
    compiled = jitted.lower(*args, **static).compile()
    ma = compiled.memory_analysis()
    used = (ma.argument_size_in_bytes + ma.output_size_in_bytes
            + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    assert used < HBM_BYTES, used
    return compiled


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


RERANKS = [64, 128]   # the serving default; chip_smoke.py's IVF-PQ depth


@pytest.mark.parametrize("rerank", RERANKS)
def test_pq_adc_scan(spec, rerank):
    c = _compile(lambda t, lc, li, s: ops.pq_adc_scan(
        t, lc, li, s, rerank, mode="kernel"),
        spec((BATCH, M, CODES), f32), spec((P, LPAD, M), u8),
        spec((P, LPAD), i32), spec((BATCH, NPROBE), i32))
    _assert_kernel(c)


@pytest.mark.parametrize("precision", ["f32", "int8"])
def test_fused_turn(spec, precision):
    c = _compile(lambda q, cn, lv, li: ops.fused_turn(
        q, cn, lv, li, nprobe=NPROBE, k=K, precision=precision,
        mode="kernel"),
        spec((BATCH, D), f32), spec((P, D), f32),
        spec((P, LPAD, D), f32), spec((P, LPAD), i32))
    _assert_kernel(c)


def test_fused_turn_pq(spec):
    c = _compile(lambda q, cn, t, lc, li, cor: ops.fused_turn_pq(
        q, cn, t, lc, li, cor, nprobe=NPROBE, k=K, rerank=64,
        mode="kernel"),
        spec((BATCH, D), f32), spec((P, D), f32),
        spec((BATCH, M, CODES), f32), spec((P, LPAD, M), u8),
        spec((P, LPAD), i32), spec((N_DOCS, D), f32))
    _assert_kernel(c)


def test_fused_scan(spec):
    c = _compile(lambda q, lv, li, s: ops.fused_scan(
        q, lv, li, s, K, mode="kernel"),
        spec((BATCH, D), f32), spec((P, LPAD, D), f32),
        spec((P, LPAD), i32), spec((BATCH, NPROBE), i32))
    _assert_kernel(c)


def test_fused_scan_pq(spec):
    c = _compile(lambda t, q, lc, li, s, cor: ops.fused_scan_pq(
        t, q, lc, li, s, cor, K, rerank=64, mode="kernel"),
        spec((BATCH, M, CODES), f32), spec((BATCH, D), f32),
        spec((P, LPAD, M), u8), spec((P, LPAD), i32),
        spec((BATCH, NPROBE), i32), spec((N_DOCS, D), f32))
    _assert_kernel(c)


def _ivf_index(spec, lmax):
    return ivf.IVFIndex(spec((P, D), f32), spec((P, lmax, D), f32),
                        spec((P, lmax), i32), spec((P,), i32))


def _pq_index(spec, lmax):
    return pq.IVFPQIndex(spec((P, D), f32), spec((M, CODES, D // M), f32),
                         spec((P, lmax, M), u8), spec((P, lmax), i32),
                         spec((P,), i32), spec((N_DOCS, D), f32))


def _session(spec):
    return toploc.IVFSession(spec((BATCH, H), i32), spec((BATCH, H, D), f32),
                             spec((BATCH, NPROBE), i32), spec((BATCH,), i32),
                             spec((BATCH,), i32))


@pytest.mark.parametrize("family,rerank", [("ivf", 0)] + [
    ("ivf_pq", r) for r in RERANKS])
@pytest.mark.parametrize("lmax", [LMAX, LPAD])
def test_step_batch(spec, monkeypatch, family, rerank, lmax):
    """The batched TopLoc+ step as the engine dispatches it on a chip:
    the kernel wrappers take their TPU branch (``default_mode`` asks the
    attached backend, which here is the CPU)."""
    monkeypatch.setattr(ops, "default_mode", lambda: "kernel")
    if family == "ivf":
        bk, index = B.IVFBackend(h=H, nprobe=NPROBE, alpha=0.1), \
            _ivf_index(spec, lmax)
    else:
        bk, index = B.IVFPQBackend(h=H, nprobe=NPROBE, alpha=0.1,
                                   rerank=rerank), _pq_index(spec, lmax)
    step = functools.partial(toploc.step_batch, bk, k=K)
    c = _compile(lambda ix, s, q, f: step(ix, s, q, is_first=f), index,
                 _session(spec), spec((BATCH, D), f32),
                 spec((BATCH,), jnp.bool_))
    if family == "ivf_pq":
        _assert_kernel(c)


SLOTS = 2048          # the CAsT configurations' slots; the slab adds trash


def _family(spec, family):
    """(backend, index) as the CAsT cells serve them on the chip: the
    XLA scan over the lists as built, or the ADC kernel over the lists
    placed at the kernel length with R = 128."""
    if family == "ivf":
        return (B.IVFBackend(h=H, nprobe=NPROBE, alpha=0.1),
                _ivf_index(spec, LMAX))
    return (B.IVFPQBackend(h=H, nprobe=NPROBE, alpha=0.1, rerank=128),
            _pq_index(spec, LPAD))


@pytest.mark.parametrize("family", ["ivf", "ivf_pq"])
def test_wave_program_moves_rows_not_the_slab(spec, monkeypatch, family):
    """The session store's wave program at CAsT width (2049-slot slab,
    B = 32): no gather copies the slab's column chunks, the slab is
    aliased through (donated, not copied), and its temporaries are the
    step's own within 256 MiB (the 32 rows are 101 MB)."""
    monkeypatch.setattr(ops, "default_mode", lambda: "kernel")
    bk, index = _family(spec, family)
    slab = toploc.IVFSession(
        spec((SLOTS + 1, H), i32), spec((SLOTS + 1, H, D), f32),
        spec((SLOTS + 1, NPROBE), i32), spec((SLOTS + 1,), i32),
        spec((SLOTS + 1,), i32))
    q, first = spec((BATCH, D), f32), spec((BATCH,), jnp.bool_)
    wave = _compile(sessions.step_batch, slab, spec((BATCH,), i32), index,
                    q, first, step=toploc.step_batch, backend=bk, k=K)
    alone = _compile(lambda ix, s, qq, f: toploc.step_batch(
        bk, ix, s, qq, k=K, is_first=f), index, _session(spec), q, first)
    assert "mini-gather" not in wave.as_text()
    ma, ma1 = wave.memory_analysis(), alone.memory_analysis()
    slab_bytes = sum(x.size * x.dtype.itemsize for x in slab)
    assert ma.alias_size_in_bytes >= slab_bytes
    assert abs(ma.temp_size_in_bytes - ma1.temp_size_in_bytes) \
        <= 256 * 2 ** 20, (ma.temp_size_in_bytes, ma1.temp_size_in_bytes)


def test_balance_assignment_fits_hbm(spec):
    """The build's balancing pass at 2^20 points x 2^14 centroids: the
    dense (n, p) similarity matrix it replaced was 64 GiB."""
    n = 1 << 20
    _compile(functools.partial(kmeans.balance_assignment, p=P,
                               capacity=LMAX),
             spec((n, D), f32), spec((P, D), f32))


BUILD_PROGRAMS = {
    # ivf.build: the k-means fit, its inertia, and the list gather
    "ivf kmeans_fit": lambda sp: (functools.partial(
        kmeans.kmeans_fit, p=P, iters=10),
        sp((N_DOCS, D), f32)),
    "ivf inertia": lambda sp: (kmeans.mean_max_similarity,
                               sp((N_DOCS, D), f32), sp((P, D), f32)),
    "ivf gather_lists": lambda sp: (ivf.gather_lists, sp((N_DOCS, D), f32),
                                    sp((P, LMAX), i32)),
    # pq.build_ivf_pq: one subspace codebook, the encode, the gather
    "pq codebook kmeans_fit": lambda sp: (functools.partial(
        kmeans.kmeans_fit, p=CODES, iters=8, init="++"),
        sp((N_DOCS, D // M), f32)),
    "pq encode": lambda sp: (pq.encode, pq.PQCodebook(
        sp((M, CODES, D // M), f32), M), sp((N_DOCS, D), f32)),
    "pq gather_lists": lambda sp: (ivf.gather_lists, sp((N_DOCS, M), u8),
                                   sp((P, LMAX), i32)),
}


@pytest.mark.parametrize("program", sorted(BUILD_PROGRAMS))
def test_build_program_fits_hbm(spec, program):
    """Every jitted program of the on-chip index build at 2^20 x 768
    (p = 2^14, m = 48) compiles for v5e under 16 GiB."""
    fn, *args = BUILD_PROGRAMS[program](spec)
    _compile(fn, *args)


@pytest.mark.parametrize("shape,fill", [((P, LMAX, D), 0.0),
                                        ((P, LMAX), -1)])
def test_placement_pad_streams_one_block(spec, shape, fill):
    """An engine whose lists a kernel scans pads them to the kernel
    length once, at placement.  The blocked pad needs one block of
    temporary beside the 4.2 GB lists and the 6.4 GB padded copy; a
    one-shot jnp.pad needs another full-size copy and ran out of HBM
    on the chip."""
    x = spec(shape, f32 if isinstance(fill, float) else i32)
    c = _compile(lambda a, v: tiling._pad_rows_blocked(
        a, LPAD, v, block_bytes=tiling.PAD_BLOCK_BYTES), x, fill)
    assert c.memory_analysis().temp_size_in_bytes \
        <= 2 * tiling.PAD_BLOCK_BYTES
