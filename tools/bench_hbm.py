#!/usr/bin/env python
"""HBM + MXU microbenchmark — the roofline inputs for PERF.md.

Measures, on the device jax was given:
  1. sustained streaming bandwidth: jit x+1 over a 1 GiB bf16 buffer
     (1 read + 1 write per element), fori_loop-chained so one dispatch
     covers the whole chain;
  2. read-reduce bandwidth: jit sum over the same buffer (1 read);
  3. bf16 matmul peak: 8192^3 chained matmuls vs the 197 TFLOP/s v5e spec.

The ResNet roofline argument leans on the streaming number, so this tool
keeps the method pinned in-tree. Every line names the platform it ran
on; only a chip run is a device number.

Prints one JSON line per metric. Timing fetches a VALUE that
data-depends on every iteration (utils/benchmarking.py discipline).
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax import lax  # noqa: E402

from distributed_tensorflow_tpu.utils.benchmarking import (  # noqa: E402
    describe_devices,
)

GIB = 1 << 30


def _timed(fn, arg, iters: int) -> float:
    """Seconds per iteration of fn chained iters times, value-fetched.

    ``arg`` is the loop CARRY (a jit parameter), so the chain is
    loop-variant by construction — XLA cannot constant-fold the buffer
    or hoist the body out of the while loop (both verified against the
    compiled HLO; a captured ``jnp.zeros``/``ones`` closure would be
    folded to a broadcast and benchmark nothing).
    """
    chained = jax.jit(
        lambda x: lax.fori_loop(0, iters, lambda _, a: fn(a), x)
    )

    def fetch(out):
        # last leaf: for a (buffer, scalar) carry that is the scalar —
        # the value that data-depends on every iteration of the chain
        return float(jnp.ravel(jax.tree.leaves(out)[-1])[0])

    fetch(chained(arg))  # compile + warmup
    t0 = time.perf_counter()
    fetch(chained(arg))  # forces execution of the whole chain
    return (time.perf_counter() - t0) / iters


def main() -> None:
    dev = describe_devices()[0][0]  # fails without a TPU or a CPU request
    print(f"device: {dev} platform={dev.platform}", file=sys.stderr)
    iters = int(os.environ.get("HBM_ITERS", "64"))

    # Fixed dispatch+fetch overhead of one timed call, which deflates
    # every short chain. Measured with the same _timed discipline on a
    # scalar body, then subtracted below; both raw and corrected values
    # are reported so the correction is auditable.
    rtt = _timed(lambda s: s + 1.0, jnp.zeros((), jnp.float32), 1)
    print(json.dumps({
        "metric": "dispatch_fetch_overhead_ms",
        "value": round(rtt * 1e3, 2), "unit": "ms",
        "platform": dev.platform,
    }))

    def corrected(per_iter: float, n_iters: int) -> float:
        # remove the one-off overhead amortized across the chain, floor at
        # 10% of the raw time so a misestimate can't produce nonsense
        return max(per_iter - rtt / n_iters, per_iter * 0.1)

    n = GIB // 2  # 1 GiB of bf16
    x = jnp.zeros((n,), jnp.bfloat16)

    dt = _timed(lambda a: a + jnp.bfloat16(1), x, iters)
    stream = 2 * GIB / corrected(dt, iters)  # read + write
    print(json.dumps({
        "metric": "hbm_stream_gbps", "value": round(stream / 1e9, 1),
        "unit": "GB/s", "platform": dev.platform, "buffer_gib": 1.0,
        "iters": iters, "raw_gbps": round(2 * GIB / dt / 1e9, 1),
    }))

    # read-reduce: the buffer rides in the carry so it stays a jit
    # parameter (a captured closure constant would be folded), and the
    # reduce is scaled by a carry-derived 1 (s*0+1 — not foldable for
    # floats, NaN/inf semantics) so each iteration's 1 GiB read is
    # loop-variant and LICM cannot hoist it out of the while loop
    def _reduce(carry):
        buf, s = carry
        one = (s * 0 + 1).astype(buf.dtype)
        return buf, s + (buf * one).sum(dtype=jnp.float32)

    dt = _timed(_reduce, (x, jnp.zeros((), jnp.float32)), iters)
    print(json.dumps({
        "metric": "hbm_reduce_gbps",
        "value": round(GIB / corrected(dt, iters) / 1e9, 1),
        "unit": "GB/s", "platform": dev.platform,
        "raw_gbps": round(GIB / dt / 1e9, 1),
    }))

    # host->device transfer bandwidth: the fed-window denominator. A
    # batch-256 ResNet input is ~77 MB; fed steps/sec is bounded by
    # transfer_bw / batch_bytes no matter how the dispatch is arranged,
    # so this one number bounds the pipeline-fed efficiency rows.
    import numpy as _np
    # random bytes: a zeros buffer could be compressed or deduplicated on
    # the way and report that, not bandwidth
    host_buf = _np.random.default_rng(0).integers(
        0, 256, 64 << 20, dtype=_np.uint8)  # 64 MiB
    jax.device_put(host_buf).block_until_ready()  # warm the path
    reps = 3
    t0 = time.perf_counter()
    for _ in range(reps):
        # += 1 defeats any content-hash/dedup cache on the way
        host_buf[:4096] += 1
        jax.device_put(host_buf).block_until_ready()
    dt = (time.perf_counter() - t0) / reps
    print(json.dumps({
        "metric": "host_to_device_gbps",
        "value": round(len(host_buf) / dt / 1e9, 3), "unit": "GB/s",
        "platform": dev.platform, "buffer_mib": 64,
    }))

    m = int(os.environ.get("MXU_DIM", "8192"))
    a = jnp.full((m, m), 1.0, jnp.bfloat16)
    # b @ b keeps both operands loop-variant; the 1/m rescale pins
    # values at 1.0 so bf16 never overflows across iterations (the
    # elementwise write is ~0.03% of the matmul time)
    scale = jnp.bfloat16(1.0 / m)
    mm_iters = max(16, iters // 4)
    dt = _timed(lambda b: (b @ b) * scale, a, mm_iters)
    tflops = 2 * m**3 / corrected(dt, mm_iters) / 1e12
    print(json.dumps({
        "metric": "mxu_bf16_tflops", "value": round(tflops, 1),
        "unit": "TFLOP/s", "platform": dev.platform, "dim": m,
        "iters": mm_iters,
        "raw_tflops": round(2 * m**3 / dt / 1e12, 1),
        "pct_of_v5e_spec": round(tflops / 197 * 100, 1),
    }))


if __name__ == "__main__":
    main()
