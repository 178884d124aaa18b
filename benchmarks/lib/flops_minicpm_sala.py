"""Operations and bytes of the `minicpm_sala` model's forward pass, from
shapes alone, whatever implements a layer. A multiply-add is two operations.
``sizes`` is the configuration file; the selection's sizes are its
``sparse_config``.

Counted per token and layer: the projections (q, k, v, o and the output
gate) and the SwiGLU. A sparse layer past ``dense_len`` tokens adds the
compressed-key scores over the units visible to each query (``2 d`` a head
and unit) and the core over the *selected* pairs only, ``2 (d + d)`` a head
and pair, whatever the kernel visits: a query ``t`` takes ``min(topk, t //
block + 1)`` blocks, its own partly (``t % block + 1`` keys) and the others
whole, since the forced window always holds its own block and every block
past it is a whole block behind. Up to ``dense_len`` tokens the core counts
the causal pairs. A lightning layer adds the scan in its chunked form at a
fixed chunk of 128 positions, a constant of the count and not the program's
choice: the lower triangles of ``q k^T`` and of the weights against ``v``
(``2 (d + d)`` a pair and head), the state built and the state read (``2
d^2`` each a token and head). Then the untied head on the rows asked for.
"""

from __future__ import annotations

import numpy as np

CHUNK = 128


def _sparse(sizes: dict) -> dict:
    return {key: int(value) for key, value in sizes["sparse_config"].items()}


def sparse_layers(sizes: dict) -> int:
    return sum(1 for kind in sizes["mixer_types"][: int(sizes["depth"])] if kind == "minicpm4")


def lightning_layers(sizes: dict) -> int:
    return int(sizes["depth"]) - sparse_layers(sizes)


def selected_pairs(sizes: dict, n_tokens: int) -> int:
    """The (query, key) pairs ``s <= t`` one KV group's selection hands the
    core, over a sequence of ``n_tokens``."""
    sp = _sparse(sizes)
    if n_tokens <= sp["dense_len"]:
        return n_tokens * (n_tokens + 1) // 2
    block, topk = sp["block_size"], sp["topk"]
    t = np.arange(n_tokens, dtype=np.int64)
    taken = np.minimum(topk, t // block + 1)
    return int(((taken - 1) * block + t % block + 1).sum())


def sparse_core_flops(sizes: dict, n_tokens: int) -> float:
    """One sparse layer's core over the selected pairs."""
    H, G, d = (int(sizes[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    return float(G * selected_pairs(sizes, n_tokens) * (H // G) * 2 * (d + d))


def sparse_core_bytes(sizes: dict, n_tokens: int, itemsize: int = 2) -> float:
    """One sparse layer's core: q, k, v read once and the output written once."""
    H, G, d = (int(sizes[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    return float(n_tokens * (2 * H * d + 2 * G * d) * itemsize)


def block_score_flops(sizes: dict, n_tokens: int) -> float:
    """One sparse layer's compressed-key scores over the visible units."""
    sp = _sparse(sizes)
    if n_tokens <= sp["dense_len"]:
        return 0.0
    w, s = sp["kernel_size"], sp["kernel_stride"]
    H, d = int(sizes["num_attention_heads"]), int(sizes["head_dim"])
    units = max((n_tokens - w) // s + 1, 0)
    t = np.arange(n_tokens, dtype=np.int64)
    visible = int(np.clip((t - w + 1) // s + 1, 0, units).sum())
    return float(visible * H * 2 * d)


def block_score_bytes(sizes: dict, n_tokens: int, itemsize: int = 2) -> float:
    """One sparse layer's block scores: q and the compressed keys read once,
    the float32 scores ``[KV groups, L, L / block]`` written once."""
    sp = _sparse(sizes)
    if n_tokens <= sp["dense_len"]:
        return 0.0
    H, G, d = (int(sizes[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    units = max((n_tokens - sp["kernel_size"]) // sp["kernel_stride"] + 1, 0)
    blocks = -(-n_tokens // sp["block_size"])
    return float((n_tokens * H + units * G) * d * itemsize + G * n_tokens * blocks * 4)


def lightning_flops(sizes: dict, n_tokens: int) -> float:
    """One lightning layer's scan in its chunked form at chunks of ``CHUNK``."""
    H, d = int(sizes["lightning_nh"]), int(sizes["lightning_head_dim"])
    full, tail = divmod(n_tokens, CHUNK)
    pairs = full * CHUNK * (CHUNK + 1) // 2 + tail * (tail + 1) // 2
    return float(H * (pairs * 2 * (d + d) + n_tokens * 2 * 2 * d * d))


def lightning_bytes(sizes: dict, n_tokens: int, itemsize: int = 2) -> float:
    """One lightning layer's scan: q, k, v read once and the output written once."""
    H, d = int(sizes["lightning_nh"]), int(sizes["lightning_head_dim"])
    return float(n_tokens * 4 * H * d * itemsize)


def lm_forward_flops(sizes: dict, n_tokens: int, n_positions: int) -> float:
    """One sequence of ``n_tokens`` with logits on ``n_positions`` rows."""
    hidden, inter = int(sizes["hidden_size"]), int(sizes["intermediate_size"])
    H, G, d = (int(sizes[k]) for k in ("num_attention_heads", "num_key_value_heads", "head_dim"))
    Hl, dl = int(sizes["lightning_nh"]), int(sizes["lightning_head_dim"])
    mlp = 2 * 3 * hidden * inter
    sparse = (n_tokens * (2 * hidden * (2 * H * d + 2 * G * d) + 2 * H * d * hidden + mlp)
              + sparse_core_flops(sizes, n_tokens) + block_score_flops(sizes, n_tokens))
    lightning = (n_tokens * (2 * hidden * 4 * Hl * dl + 2 * Hl * dl * hidden + mlp)
                 + lightning_flops(sizes, n_tokens))
    return float(sparse_layers(sizes) * sparse + lightning_layers(sizes) * lightning
                 + 2 * n_positions * hidden * int(sizes["vocab_size"]))
