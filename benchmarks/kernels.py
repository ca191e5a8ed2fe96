"""Kernel micro-benchmarks: host-clock times of the jnp reference
paths (``mode="ref"``) on whatever backend JAX starts.  On a CPU these
are CPU timings, not device numbers.  Run through ``benchmarks/run.py
--only kernels`` or directly."""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import time_fn
from repro.kernels import ops, ref


def bench_kernels() -> None:
    """Kernel micro-benchmarks (jnp ref path timing on CPU; the Pallas
    kernels themselves are TPU-target and validated via interpret)."""
    rng = np.random.default_rng(0)

    q = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))
    c = jnp.asarray(rng.normal(size=(4096, 64)).astype(np.float32))
    f = jax.jit(lambda q, c: ref.centroid_topk(q, c, 64))
    t = time_fn(f, q, c)
    print(f"kernel.centroid_topk_ref,{1e6*t:.1f},p=4096 d=64 b=16")

    lv = jnp.asarray(rng.normal(size=(128, 256, 64)).astype(np.float32))
    li = jnp.asarray(rng.integers(0, 10**6, (128, 256)).astype(np.int32))
    sel = jnp.asarray(np.stack([rng.permutation(128)[:16]
                                for _ in range(16)]).astype(np.int32))
    f = jax.jit(lambda q, s: ref.ivf_scan_batch(q, lv, li, s, 10))
    t = time_fn(f, q, sel)
    print(f"kernel.ivf_scan_ref,{1e6*t:.1f},np=16 Lmax=256 b=16")

    cf = jnp.asarray(rng.normal(size=(128, 64)).astype(np.float32))
    f = jax.jit(lambda q: ops.fused_turn(q, cf, lv, li, nprobe=16, k=10,
                                         mode="ref")[:2])
    t = time_fn(f, q)
    print(f"kernel.fused_turn_ref,{1e6*t:.1f},p=128 np=16 Lmax=256 b=16")

    qa = jnp.asarray(rng.normal(size=(2, 8, 1024, 64)).astype(np.float32))
    ka = jnp.asarray(rng.normal(size=(2, 2, 1024, 64)).astype(np.float32))
    f = jax.jit(lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                                    mode="ref"))
    t = time_fn(f, qa, ka, ka)
    print(f"kernel.attention_ref,{1e6*t:.1f},b2 h8 s1024 d64")

    table = jnp.asarray(rng.normal(size=(100000, 32)).astype(np.float32))
    ids = jnp.asarray(rng.integers(-1, 100000, (4096, 20)).astype(np.int32))
    f = jax.jit(lambda t_, i_: ops.embedding_bag(t_, i_, mode="ref"))
    t = time_fn(f, table, ids)
    print(f"kernel.embedding_bag_ref,{1e6*t:.1f},V=1e5 b=4096 L=20")



if __name__ == "__main__":
    print(f"# device: {jax.devices()[0].platform} "
          f"{jax.devices()[0].device_kind} x{jax.device_count()}")
    print("name,us_per_call,derived")
    bench_kernels()
