"""Operations and bytes of the `axk1` model's forward pass, from shapes
alone, whatever implements a layer (``lib/flops_lm.py`` has the Granite
hybrid's). A multiply-add is two operations. ``sizes`` is the configuration
file. What is counted is the published, un-absorbed forward:

per token and layer, latent attention's five projections (``q_a`` hidden ->
q rank, ``q_b`` q rank -> heads x (nope + rope), ``kv_a`` hidden -> kv rank +
rope, ``kv_b`` kv rank -> heads x (nope + v) on every token, ``o`` heads x v
-> hidden) and its causal core as the lower triangle, 2 x heads x ((nope +
rope) + v) x L / 2; the dense layers' gated MLP (2 x 3 x hidden x
``intermediate_size``); in an expert layer the router over the published
``n_routed_experts``, the held experts at their expected load, ``top_k x held
/ published`` choices a token (each 2 x 3 x hidden x ``moe_intermediate_size``),
and the shared expert; and the untied head on the rows asked for. Norms,
rotations, the softmax and the gate's sigmoid are not counted.
"""

from __future__ import annotations


def _published(sizes: dict, key: str):
    return sizes.get("published", {}).get(key, sizes[key])


def _layers(sizes: dict):
    """(all layers run here, the expert layers among them)."""
    depth = int(sizes["depth"])
    return depth, max(depth - int(sizes["first_k_dense_replace"]), 0)


def _head_widths(sizes: dict):
    """(heads, a key's width, a value's width)."""
    return (int(sizes["num_attention_heads"]),
            int(sizes["qk_nope_head_dim"]) + int(sizes["qk_rope_head_dim"]),
            int(sizes["v_head_dim"]))


def attention_projection_flops_per_token(sizes: dict) -> float:
    d, (heads, qk, v) = int(sizes["hidden_size"]), _head_widths(sizes)
    q_rank, kv_rank = int(sizes["q_lora_rank"]), int(sizes["kv_lora_rank"])
    nope, rope = int(sizes["qk_nope_head_dim"]), int(sizes["qk_rope_head_dim"])
    return 2.0 * (d * q_rank + q_rank * heads * qk + d * (kv_rank + rope)
                  + kv_rank * heads * (nope + v) + heads * v * d)


def expert_flops_per_row(sizes: dict) -> float:
    """One token through one expert: ``W1`` (hidden -> 2 width) and ``W2``."""
    return 2.0 * 3 * int(sizes["hidden_size"]) * int(sizes["moe_intermediate_size"])


def lm_forward_flops(sizes: dict, n_tokens: int, n_positions: int) -> float:
    """One sequence of ``n_tokens`` with logits on ``n_positions`` rows."""
    d, L = int(sizes["hidden_size"]), n_tokens
    heads, qk, v = _head_widths(sizes)
    depth, expert_layers = _layers(sizes)
    attention = attention_projection_flops_per_token(sizes) + 2.0 * heads * (qk + v) * (L / 2)
    dense = 2.0 * 3 * d * int(sizes["intermediate_size"])
    choices = int(sizes["num_experts_per_tok"]) * int(sizes["n_routed_experts"]) \
        / int(_published(sizes, "n_routed_experts"))
    moe = 2.0 * d * int(_published(sizes, "n_routed_experts")) \
        + (choices + int(sizes["n_shared_experts"])) * expert_flops_per_row(sizes)
    per_token = depth * attention + (depth - expert_layers) * dense + expert_layers * moe
    return float(L * per_token + 2.0 * n_positions * d * int(sizes["vocab_size"]))


def attention_core_flops(sizes: dict, n_tokens: int) -> float:
    """QK^T over the keys' width and PV over the values', every layer, the
    lower triangle."""
    heads, qk, v = _head_widths(sizes)
    return float(_layers(sizes)[0] * 2.0 * heads * (qk + v) * (n_tokens * n_tokens / 2))


def attention_core_bytes(sizes: dict, n_tokens: int, itemsize: int = 2) -> float:
    """q and k read once at the keys' width, v read and out written once at
    the values', a layer."""
    heads, qk, v = _head_widths(sizes)
    return float(_layers(sizes)[0] * n_tokens * heads * (2 * qk + 2 * v) * itemsize)


def grouped_matmul_flops(sizes: dict, rows: int) -> float:
    """Both grouped products over ``rows`` (token, expert) choices routed here."""
    return rows * expert_flops_per_row(sizes)


def grouped_matmul_bytes(sizes: dict, rows: int, layers: int, itemsize: int = 2) -> float:
    """Every held expert's two matrices read once a layer pass, and each
    routed row in and out of both products. ``layers``: expert-layer passes."""
    d, w = int(sizes["hidden_size"]), int(sizes["moe_intermediate_size"])
    weights = layers * int(sizes["n_routed_experts"]) * 3 * d * w
    return float((weights + rows * (d + 2 * w + w + d)) * itemsize)
