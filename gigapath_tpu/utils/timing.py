"""Device-time measurement by chaining iterations inside one jitted loop.

The recipe: run the op N times *inside one jitted fori_loop* with a forced
cross-iteration data dependency (so XLA cannot hoist the body), fetch one
scalar, and difference two loop counts to cancel the fixed per-call cost
(dispatch, the scalar fetch).

On a directly attached chip ``block_until_ready`` does wait for the device,
so plain host timing around it is a valid measurement too: it reads the same
op plus one dispatch per call. ``chip_smoke.py`` phase A prints both for the
fused dilated-attention forward at 10,241 tokens; on one TPU v5e they read
4.93 ms per iteration chained (fixed overhead 1.40 ms per call, cancelled)
and 5.73 ms median per call by the host clock — 16 % apart, the per-call
dispatch (chip run of PR 21; set-up facts of one run, not a benchmark). The
chained recipe matters for ops of a few milliseconds and below, where that
dispatch is a visible share; for a 100 ms step either will do.
"""

from __future__ import annotations

import time
from typing import Callable, Tuple

import jax
import jax.numpy as jnp


def chained_seconds_per_iter(
    step: Callable[..., jnp.ndarray],
    x0: jnp.ndarray,
    *,
    args: Tuple = (),
    iters_low: int = 2,
    iters_high: int = 12,
    repeats: int = 2,
) -> Tuple[float, float]:
    """Median seconds/iter of ``step(carry, *args) -> carry``.

    Returns ``(sec_per_iter, overhead_sec)``. Pass model params and other
    large arrays via ``args`` — NOT by closing over them: closure constants
    are inlined into the lowered program.
    """

    def chain(x, extra, n):
        def body(_, carry):
            return step(carry, *extra)

        return jax.lax.fori_loop(0, n, body, x).sum()

    lo = jax.jit(lambda x, extra: chain(x, extra, iters_low))
    hi = jax.jit(lambda x, extra: chain(x, extra, iters_high))
    float(lo(x0, args))  # compile
    float(hi(x0, args))

    def timed(f):
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            float(f(x0, args))
            best = min(best, time.perf_counter() - t0)  # gigalint: waive GL008 -- this IS the sanctioned fence: the float() scalar fetch syncs the chained fori_loop, and differencing two loop counts cancels the round-trip
        return best

    t_lo, t_hi = timed(lo), timed(hi)
    per_iter = (t_hi - t_lo) / (iters_high - iters_low)
    overhead = t_lo - iters_low * per_iter
    return max(per_iter, 1e-9), overhead
