"""Operations and bytes the configurations' algorithms need, from shapes
alone: whatever implements a layer, these are what it is measured against.

Copies of ``bench.py``'s ``workload_flops`` and ``tile_workload_flops``
(sound arithmetic, PERF.md inventory), taking the configuration file's
``sizes`` in place of module constants, plus the attention-only and backward
counts. A multiply-add is two operations; recomputation is never counted.
"""

from __future__ import annotations

from benchmarks.lib.reference import segment_schedule


def tile_forward_flops(sizes: dict) -> float:
    """One tile through the ViT: qkv + proj (4 d^2), packed SwiGLU (fc1
    d -> hidden, fc2 hidden/2 -> d: 3 d hidden), attention (4 L d), per token
    and layer, and the patch embedding."""
    grid = int(sizes["img_size"]) // int(sizes["patch_size"])
    L = grid * grid + 1
    d = int(sizes["embed_dim"])
    hidden = int(d * float(sizes["mlp_ratio"]))
    p = int(sizes["patch_size"])
    per_layer = 4 * 2 * L * d * d + 3 * L * d * hidden + 4 * L * L * d
    return float(int(sizes["depth"]) * per_layer + 2 * L * 3 * p * p * d)


def _windows(sizes: dict, L: int) -> float:
    """Sum over branches of m / r: each head attends m = ceil(min(s, L) / r)
    keys from 1 / r of the positions."""
    return sum(
        -(-min(s, L) // int(r)) / int(r)
        for s, r in zip(segment_schedule(sizes), sizes["dilated_ratio"])
    )


def slide_attention_forward_flops(sizes: dict, n_tokens: int) -> float:
    """QK^T and PV of every branch and layer for one slide of ``n_tokens``
    tiles (+ the class token): per branch 4 E L m / r."""
    L = n_tokens + 1
    E = int(sizes["embed_dim"])
    return float(int(sizes["depth"]) * 4 * L * E * _windows(sizes, L))


def slide_attention_backward_flops(sizes: dict, n_tokens: int) -> float:
    """dQ, dK, dV and dP: twice the forward (the recomputed QK^T is not
    counted)."""
    return 2.0 * slide_attention_forward_flops(sizes, n_tokens)


def slide_attention_bytes(sizes: dict, n_tokens: int, itemsize: int = 2) -> float:
    """q, k, v read and o written once per branch and layer at the compute
    type: a branch of ratio r touches 1 / r of the positions in every head."""
    L = n_tokens + 1
    E = int(sizes["embed_dim"])
    per_branch = sum(4 * (L / int(r)) * E * itemsize for r in sizes["dilated_ratio"])
    return float(int(sizes["depth"]) * per_branch)


def slide_forward_flops(sizes: dict, n_tokens: int) -> float:
    """One slide forward: q/k/v/out projections and the FFN per layer,
    attention, and the patch embedding."""
    L = n_tokens + 1
    E = int(sizes["embed_dim"])
    ffn = int(E * float(sizes["mlp_ratio"]))
    gemms = int(sizes["depth"]) * (4 * 2 * L * E * E + 2 * 2 * L * E * ffn)
    patch = 2 * L * int(sizes["in_chans"]) * E
    return float(gemms + patch) + slide_attention_forward_flops(sizes, n_tokens)


def slide_train_flops(sizes: dict, n_tokens: int) -> float:
    """Forward plus backward (twice the forward) over the valid tokens."""
    return 3.0 * slide_forward_flops(sizes, n_tokens)
