"""Wall-clock timing of a jitted call, for the kernel microbenchmarks."""
from __future__ import annotations

import time


def bench_fn(fn, *args, warmup: int = 1, iters: int = 5) -> float:
    """Return mean microseconds per call of ``fn(*args)`` (blocks on jax)."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e6 * (time.perf_counter() - t0) / iters
