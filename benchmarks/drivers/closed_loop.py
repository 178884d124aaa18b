"""Closed loop with a fixed number of requests in flight (the traffic
file's ``in_flight``, 1 where it says nothing): a new batch is sent whenever
fewer than that many are out, and the oldest answer is fetched when the
number is reached. Each request goes host batch in -> the entry's own
conversion to the device -> the program's jitted function -> the entry's own
conversion back to the host, as ``pipeline.run_inference_with_*`` does; with
1 in flight strictly in that order, with more the host's part of the next
requests runs while the device works on the earlier ones, so that the chip
stays fed while the host stands still.

The window opens when set-up is done. At ``--seconds`` nothing more is sent;
the window closes when every answer that was sent for is on the host, and the
clock is read after that wait: the rate is all the work of the window over
all its time.
"""

from __future__ import annotations

import collections
import time

import numpy as np

from benchmarks.lib.trace import WINDOW_SPAN


def run(ctx) -> dict:
    """``ctx``: the harness's run context (``run.py``). Returns the window
    record the metrics are computed from."""
    import jax

    system, traffic = ctx.system, ctx.traffic
    n_distinct = int(traffic["distinct_batches"])
    in_flight = int(traffic.get("in_flight", 1))

    # ---- set-up: weights on the device from the seed, inputs, warm-up
    params = ctx.make_weights(system.param_shapes())
    fn = system.make_fn()
    jax.block_until_ready(params)
    ctx.mark("weights")
    rng = np.random.default_rng(ctx.seed)
    batches = [system.host_batch(rng, traffic) for _ in range(n_distinct)]
    order = rng.permutation(n_distinct)
    ctx.mark("host_inputs")
    for b in range(min(2, n_distinct)):  # the window's one shape, twice
        system.to_host(fn(params, *system.to_device(batches[b])))
        ctx.mark(f"warm_up_{b}")
    ctx.setup_done()

    # ---- the measured window
    outputs, served, pending = [], [], collections.deque()
    span = ctx.spans.span
    with ctx.tracing():
        with span(WINDOW_SPAN):
            compiles_before = ctx.compile_meter.compiles
            t0 = time.perf_counter()
            deadline = t0 + ctx.seconds
            sending = True
            while sending or pending:
                sending = sending and time.perf_counter() < deadline
                if sending:
                    which = int(order[len(served) % n_distinct])
                    with span("h2d"):
                        dev = system.to_device(batches[which])
                    pending.append(fn(params, *dev))
                    served.append(which)
                if pending and (not sending or len(pending) >= in_flight):
                    with span("fetch"):
                        outputs.append(system.to_host(pending.popleft()))
            t1 = time.perf_counter()
    del dev
    for name in ("h2d", "fetch"):  # where a stall of the host fell, if one did
        took, at = max((end - start, start) for n, start, end in ctx.spans.spans if n == name)
        ctx.notes.append(f"longest {name}: {took / 1e9:.3f} s, "
                         f"{at / 1e9 - t0:.1f} s into the window")

    return {
        "seconds": t1 - t0,
        "attempted": len(served),
        "failed": sum(1 for o in outputs if not np.isfinite(o).all()),
        "work": sum(system.work(batches[w]) for w in served),
        "flops": sum(system.flops(batches[w]) for w in served),
        "items": [n for w in served for n in system.items(batches[w])],
        "compiles": ctx.compile_meter.compiles - compiles_before,
        "_state": (params, batches, served, outputs),
    }


def sampled(ctx, window):
    """A sample of the window's answers, drawn from the seed and with the
    last request in it: ``(host batch, rows, the answers in those rows)``."""
    _, batches, served, outputs = window["_state"]
    system, want = ctx.system, ctx.cell["correct"]
    rng = np.random.default_rng(ctx.seed + 1)
    last = len(served) - 1
    others = rng.permutation(last)[: int(want["requests"]) - 1]
    for idx in sorted({last, *map(int, others)}):
        batch = batches[served[idx]]
        rows = np.sort(rng.permutation(system.rows(batch))[: int(want["rows"])])
        yield batch, rows, outputs[idx][rows]


def check(ctx, window, stand_in=None) -> dict:
    """Compare the sampled answers row by row with the plain reference.
    Called once the window has closed and the memory peak has been read.
    ``stand_in`` names a precision: the reference computed in it takes the
    program's place (the control, ``benchmarks/readings.py``)."""
    params, system = window["_state"][0], ctx.system
    gaps = []
    for batch, rows, got in sampled(ctx, window):
        ref = system.reference(params, batch, rows, "f32")
        if stand_in is not None:
            got = system.reference(params, batch, rows, stand_in)
        gaps.append(row_gaps(got, ref))
    return summarise(np.concatenate(gaps))


def row_gaps(got: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """Per answer (last axis an embedding): |got - ref| / |ref|."""
    got = np.asarray(got, np.float64).reshape(-1, got.shape[-1])
    ref = np.asarray(ref, np.float64).reshape(-1, ref.shape[-1])
    return np.linalg.norm(got - ref, axis=-1) / np.linalg.norm(ref, axis=-1)


def summarise(gaps: np.ndarray) -> dict:
    """The two numbers compared: the widest gap of a single answer, and the
    mean gap, which is steadier from seed to seed. An answer that is not
    finite counts as a gap of 1e30, which no limit admits and JSON can hold."""
    gaps = np.nan_to_num(gaps, nan=1e30, posinf=1e30)
    return {"embed_gap_max": float(gaps.max()), "embed_gap_mean": float(gaps.mean())}
