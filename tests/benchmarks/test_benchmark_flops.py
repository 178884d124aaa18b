"""``benchmarks/lib/flops.py`` and ``peaks.py``: the yardstick's arithmetic."""

import pytest

from benchmarks.lib import flops, peaks, tables

SLIDE = tables.load("configs", "gigapath_slide_enc12l768d")
TILE = tables.load("configs", "gigapath_tile_enc")


def test_slide_forward_reproduces_bench_py():
    import bench

    assert flops.slide_forward_flops(SLIDE, 10240) == pytest.approx(
        bench.workload_flops(10240), rel=1e-12)
    assert flops.slide_forward_flops(SLIDE, 10240) == pytest.approx(3.0e12, rel=0.02)


def test_tile_forward_reproduces_bench_py():
    import bench
    from gigapath_tpu.models.tile_encoder import gigapath_tile_enc

    assert flops.tile_forward_flops(TILE) == pytest.approx(
        bench.tile_workload_flops(gigapath_tile_enc()), rel=1e-12)
    assert flops.tile_forward_flops(TILE) == pytest.approx(0.456e12, rel=0.01)


@pytest.mark.parametrize("n", [4097, 10240, 16384])
def test_attention_counts_follow_the_stated_formulas(n):
    L, E, depth = n + 1, 768, 12
    segs = [1024, 5792, 32768, 185363, 1048576]
    ratios = [1, 2, 4, 8, 16]
    fwd = sum(4 * E * L * -(-min(s, L) // r) / r for s, r in zip(segs, ratios)) * depth
    assert flops.slide_attention_forward_flops(SLIDE, n) == pytest.approx(fwd, rel=1e-12)
    assert flops.slide_attention_backward_flops(SLIDE, n) == pytest.approx(2 * fwd, rel=1e-12)
    nbytes = sum(4 * (L / r) * E * 2 for r in ratios) * depth
    assert flops.slide_attention_bytes(SLIDE, n) == pytest.approx(nbytes, rel=1e-12)
    assert flops.slide_train_flops(SLIDE, n) == pytest.approx(
        3 * flops.slide_forward_flops(SLIDE, n), rel=1e-12)


def test_v5e_peaks_and_unknown_kind():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks on record"):
        peaks.peaks_for("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")
