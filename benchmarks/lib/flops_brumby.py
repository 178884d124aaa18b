"""Operations and bytes of the `brumby` model's forward pass, from shapes
alone, whatever implements a layer. A multiply-add is two operations.
``sizes`` is the configuration file.

Counted per token and layer: the four projections and the gate's; the SwiGLU;
and power retention in its chunked form with the feature expansion of ``D =
d (d + 1) / 2`` entries: the state built over the KV heads and read over the
query heads, each with the normaliser's column (``2 D (d + 1)`` a token and
head), and inside each chunk the lower triangle of ``q . k`` (``2 d`` a pair)
and of the weights against ``[v | 1]`` (``2 (d + 1)`` a pair). The chunk is
a fixed 128 positions, a constant of the count and not the program's choice,
so that the yardstick stays put when the program's chunk moves. Then the
untied head on the rows asked for.
"""

from __future__ import annotations

CHUNK = 128


def _heads(sizes: dict):
    return (int(sizes["num_attention_heads"]), int(sizes["num_key_value_heads"]),
            int(sizes["head_dim"]))


def retention_flops(sizes: dict, n_tokens: int) -> float:
    """One layer's retention over a sequence of ``n_tokens``."""
    H, G, d = _heads(sizes)
    D = d * (d + 1) // 2
    state = n_tokens * 2 * D * (d + 1) * (G + H)
    full, tail = divmod(n_tokens, CHUNK)
    pairs = full * CHUNK * (CHUNK + 1) // 2 + tail * (tail + 1) // 2
    return float(state + pairs * H * (2 * d + 2 * (d + 1)))


def retention_bytes(sizes: dict, n_tokens: int, itemsize: int = 2) -> float:
    """One layer's retention: q, k, v read once, the float32 gate read once,
    y written once."""
    H, G, d = _heads(sizes)
    return float(n_tokens * ((2 * H * d + 2 * G * d) * itemsize + G * 4))


def lm_forward_flops(sizes: dict, n_tokens: int, n_positions: int) -> float:
    """One sequence of ``n_tokens`` with logits on ``n_positions`` rows."""
    H, G, d = _heads(sizes)
    hidden = int(sizes["hidden_size"])
    projections = 2 * hidden * (H * d + 2 * G * d) + 2 * H * d * hidden + 2 * hidden * G
    mlp = 2 * 3 * hidden * int(sizes["intermediate_size"])
    per_layer = n_tokens * (projections + mlp) + retention_flops(sizes, n_tokens)
    return float(int(sizes["depth"]) * per_layer
                 + 2 * n_positions * hidden * int(sizes["vocab_size"]))
