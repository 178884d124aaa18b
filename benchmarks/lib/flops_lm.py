"""Operations and bytes of the `granite_hybrid` model's forward pass, from shapes
alone, whatever implements a layer (``lib/flops.py`` has the encoders'). A
multiply-add is two operations. ``sizes`` is the configuration file.

Counted per token and layer: the Mamba-2 projections (``hidden -> 2 inner +
2 state + heads`` and ``inner -> hidden``); the state-space scan in its
chunked dual form at the published chunk ``Q`` (``C B^T`` 2 Q N, the masked
product 2 Q inner, the chunk's state 2 N inner, the carried state's part
2 N inner); attention's four projections and its causal core as the lower
triangle (4 x L / 2 x heads x head size); the router; the held experts at
their expected load, ``top_k x held / total`` choices a token (each 2 x 3 x
hidden x width); the shared MLP; and the tied head on the rows asked for.
"""

from __future__ import annotations


def _kinds(sizes: dict) -> list:
    return list(sizes["layer_types"][: int(sizes["depth"])])


def _published(sizes: dict, key: str):
    return sizes.get("published", {}).get(key, sizes[key])


def expert_flops_per_row(sizes: dict) -> float:
    """One token through one expert: ``W1`` (hidden -> 2 width) and ``W2``."""
    return 2.0 * 3 * int(sizes["hidden_size"]) * int(sizes["intermediate_size"])


def lm_forward_flops(sizes: dict, n_tokens: int, n_positions: int) -> float:
    """One sequence of ``n_tokens`` with logits on ``n_positions`` rows."""
    d, L = int(sizes["hidden_size"]), n_tokens
    heads, state = int(sizes["mamba_n_heads"]), int(sizes["mamba_d_state"])
    inner, Q = heads * int(sizes["mamba_d_head"]), int(sizes["mamba_chunk_size"])
    head_dim = d // int(sizes["num_attention_heads"])
    kv = int(sizes["num_key_value_heads"]) * head_dim
    mamba = 2 * d * (2 * inner + 2 * state + heads) + 2 * inner * d \
        + 2 * Q * state + 2 * Q * inner + 4 * state * inner
    attention = 2 * 2 * d * d + 2 * 2 * d * kv + 4 * (L / 2) * d
    choices = int(sizes["num_experts_per_tok"]) * int(sizes["num_local_experts"]) \
        / int(_published(sizes, "num_local_experts"))
    moe = 2 * d * int(_published(sizes, "num_local_experts")) \
        + choices * expert_flops_per_row(sizes) \
        + 2 * 3 * d * int(sizes["shared_intermediate_size"])
    per_token = sum((mamba if k == "mamba" else attention) + moe for k in _kinds(sizes))
    return float(L * per_token + 2 * n_positions * d * int(sizes["vocab_size"]))


def attention_core_flops(sizes: dict, n_tokens: int) -> float:
    """QK^T and PV of every attention layer, the lower triangle."""
    layers = _kinds(sizes).count("attention")
    return float(layers * 4 * (n_tokens * n_tokens / 2) * int(sizes["hidden_size"]))


def attention_core_bytes(sizes: dict, n_tokens: int, itemsize: int = 2) -> float:
    """q read and o written once, k and v (the KV heads) read once, a layer."""
    d = int(sizes["hidden_size"])
    kv = int(sizes["num_key_value_heads"]) * (d // int(sizes["num_attention_heads"]))
    return float(_kinds(sizes).count("attention") * n_tokens * (2 * d + 2 * kv) * itemsize)


def grouped_matmul_flops(sizes: dict, rows: int) -> float:
    """Both grouped products over ``rows`` (token, expert) choices routed here."""
    return rows * expert_flops_per_row(sizes)


def grouped_matmul_bytes(sizes: dict, rows: int, layers: int, itemsize: int = 2) -> float:
    """Every held expert's two matrices read once a layer and request, and
    each routed row in and out of both products."""
    d, w = int(sizes["hidden_size"]), int(sizes["intermediate_size"])
    weights = layers * int(sizes["num_local_experts"]) * 3 * d * w
    return float((weights + rows * (d + 2 * w + w + d)) * itemsize)
