"""On-chip correctness gate for the Pallas attention paths.

The pytest suite runs on a virtual CPU mesh (tests/conftest.py) where the
Pallas kernels execute in interpret mode; this script validates the REAL
compiled kernels on the local TPU against the float32 jnp reference at bf16
tolerances, plus gradients through the custom-vjp backward kernels. The
checks themselves live in ``gigapath_tpu/utils/kernel_checks.py`` —
``chip_smoke.py`` phase A runs the same function; this script adds the
default-off env-flagged kernel variants.

Run: python scripts/tpu_selfcheck.py   (exits nonzero on any failure, and
when the default backend is not a TPU: there is nothing it can vouch for)
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    import jax

    from gigapath_tpu.utils.compile_cache import enable_compile_cache
    from gigapath_tpu.utils.kernel_checks import flagship, run_kernel_checks

    if jax.default_backend() != "tpu":
        print(
            f"tpu_selfcheck: default backend is {jax.default_backend()!r}, "
            "not 'tpu' — the compiled kernels were NOT checked",
            file=sys.stderr,
        )
        return 2
    enable_compile_cache()
    from bench import N  # stay in lockstep with the driver's bench

    t0 = time.time()

    def report(row):
        print(
            f"[{time.time() - t0:6.1f}s] {row['name']:55s} "
            f"max_err={row['max_abs_err']:.4e} (atol {row['atol']:g})  "
            f"{'ok' if row['ok'] else 'FAIL'}"
        )

    rows = run_kernel_checks(flagship(N), flagged_variants=True, report=report)
    failed = [r["name"] for r in rows if not r["ok"]]
    if failed:
        print("FAILED:", failed)
        return 1
    print(f"all {len(rows)} on-chip checks passed in {time.time() - t0:.1f}s")  # gigalint: waive GL008 -- whole-script wall; every check already fetched its operands to the host
    return 0


if __name__ == "__main__":
    sys.exit(main())
