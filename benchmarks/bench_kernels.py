"""Kernel-layer throughput: us/call for the pure-jnp reference paths that the
toggle-count, WS-matmul and attention kernels are validated against, and for
the host-side numpy profiler. No Pallas kernel runs here, in interpret mode
or otherwise; the activity-profiling kernels are timed through the engines
in ``bench_activity_profile`` and ``bench_network_profile``. Every number is
a wall-clock time on whatever backend JAX selected.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.switching import profile_gemm
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.toggle_count.ref import stream_toggle_count_ref
from repro.kernels.ws_matmul.ref import ws_matmul_ref


def _time(fn, *args, iters=5) -> float:
    fn(*args)  # compile/warm
    t0 = time.time()
    for _ in range(iters):
        r = fn(*args)
    jax.block_until_ready(r) if hasattr(r, "block_until_ready") else None
    return (time.time() - t0) * 1e6 / iters


def run() -> list[dict]:
    rng = np.random.default_rng(0)
    out = []

    s = jnp.asarray(rng.integers(-(2**31), 2**31, size=(4096, 256), dtype=np.int64).astype(np.int32))
    f = jax.jit(stream_toggle_count_ref)
    us = _time(f, s)
    out.append(
        {
            "name": "kernel/toggle_count_ref_4096x256",
            "us_per_call": round(us, 1),
            "derived": f"{4096*256*4/us*1e6/2**30:.2f} GiB/s",
        }
    )

    a = jnp.asarray(rng.integers(-127, 127, size=(512, 512)), dtype=jnp.int8)
    w = jnp.asarray(rng.integers(-127, 127, size=(512, 512)), dtype=jnp.int8)
    f = jax.jit(ws_matmul_ref)
    us = _time(f, a, w)
    out.append(
        {
            "name": "kernel/ws_matmul_ref_512x512x512_int8",
            "us_per_call": round(us, 1),
            "derived": f"{2*512**3/us/1e3:.1f} GFLOP/s-int",
        }
    )

    q = jnp.asarray(rng.normal(size=(4, 256, 64)), dtype=jnp.float32)
    f = jax.jit(lambda q: attention_ref(q, q, q, causal=True))
    us = _time(f, q)
    out.append(
        {
            "name": "kernel/attention_ref_b4_s256_d64",
            "us_per_call": round(us, 1),
            "derived": f"{4*2*2*256*256*64/us/1e3:.1f} GFLOP/s",
        }
    )

    a_np = rng.integers(0, 1000, size=(256, 64))
    w_np = rng.integers(-1000, 1000, size=(64, 64))
    t0 = time.time()
    profile_gemm(a_np, w_np, 32, 32, 16, 37, backend="numpy", use_cache=False)
    us = (time.time() - t0) * 1e6
    out.append(
        {
            "name": "profiler/ws_gemm_256x64x64",
            "us_per_call": round(us, 1),
            "derived": "switching-activity profile (numpy oracle; fused engine in bench_activity_profile)",
        }
    )
    return out


if __name__ == "__main__":
    for r in run():
        print(r)
