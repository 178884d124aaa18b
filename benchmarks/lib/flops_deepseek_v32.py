"""Operations and bytes of the `deepseek_v32` model's forward pass, from
shapes alone, whatever implements a layer (``lib/flops_axk1.py`` has the
DeepSeek-V3 layer's; its counts are taken from there). A multiply-add is two
operations. ``sizes`` is the configuration file. What is counted is the least
work the published, un-absorbed forward asks for:

per token and layer, latent attention's five projections as A.X-K1 has them
and the indexer's three (``wq_b`` q rank -> index heads x index dim, ``wk``
hidden -> index dim, ``weights_proj`` hidden -> index heads); the index
scores over the *causal* pairs, 2 x index heads x index dim a pair (L (L + 1)
/ 2 pairs: every earlier key has to be scored before any can be left out);
the core over the *selected* pairs, sum_t min(t + 1, index_topk) of them, 2 x
heads x ((nope + rope) + v) a pair: a core that visits more (a dense causal
core with the selection as a mask) is not credited with what it visits, so
neither ``step_mfu`` nor a roofline can pass 100 %; the dense layers' MLP,
the expert layers and the head as ``lib/flops_axk1.py`` counts them; and,
where the share runs the prediction module (``num_nextn_predict_layers`` 1),
its ``2 hidden -> hidden`` projection, one more expert layer with its
attention, and the head once more. Norms, rotations, the softmax, the top-k
and the gate's sigmoid are not counted.
"""

from __future__ import annotations

from benchmarks.lib import flops_axk1
from benchmarks.lib.flops_axk1 import expert_flops_per_row


def causal_pairs(n_tokens: int) -> int:
    return n_tokens * (n_tokens + 1) // 2


def selected_pairs(sizes: dict, n_tokens: int) -> int:
    """sum over t of min(t + 1, index_topk): what an exact selection hands the core."""
    k = min(int(sizes["index_topk"]), n_tokens)
    return k * (k + 1) // 2 + (n_tokens - k) * k


def _index_widths(sizes: dict):
    return int(sizes["index_n_heads"]), int(sizes["index_head_dim"])


def indexer_projection_flops_per_token(sizes: dict) -> float:
    heads, dim = _index_widths(sizes)
    d = int(sizes["hidden_size"])
    return 2.0 * (int(sizes["q_lora_rank"]) * heads * dim + d * dim + d * heads)


def index_score_flops_per_layer(sizes: dict, n_tokens: int) -> float:
    heads, dim = _index_widths(sizes)
    return 2.0 * heads * dim * causal_pairs(n_tokens)


def index_score_bytes_per_layer(sizes: dict, n_tokens: int, itemsize: int = 2) -> float:
    """Every query head and the one key read once, the head weights read and
    the causal half of the scores written once in float32."""
    heads, dim = _index_widths(sizes)
    return float(n_tokens * (heads * dim + dim) * itemsize + n_tokens * heads * 4
                 + causal_pairs(n_tokens) * 4)


def sparse_core_flops_per_layer(sizes: dict, n_tokens: int) -> float:
    heads, qk, v = flops_axk1._head_widths(sizes)
    return 2.0 * heads * (qk + v) * selected_pairs(sizes, n_tokens)


def sparse_core_bytes_per_layer(sizes: dict, n_tokens: int, itemsize: int = 2) -> float:
    """q and k once at the keys' width, v and out once at the values', and a
    byte a causal pair for the selection."""
    heads, qk, v = flops_axk1._head_widths(sizes)
    return float(n_tokens * heads * (2 * qk + 2 * v) * itemsize + causal_pairs(n_tokens))


def attention_layers(sizes: dict) -> int:
    """Layers with an attention here: the stack's and the prediction module's."""
    return int(sizes["depth"]) + int(sizes["num_nextn_predict_layers"])


def expert_layers(sizes: dict) -> int:
    return flops_axk1._layers(sizes)[1] + int(sizes["num_nextn_predict_layers"])


def lm_forward_flops(sizes: dict, n_tokens: int, n_positions: int) -> float:
    """One sequence of ``n_tokens`` with logits on ``n_positions`` rows."""
    d, L = int(sizes["hidden_size"]), n_tokens
    depth, _ = flops_axk1._layers(sizes)
    mtp = int(sizes["num_nextn_predict_layers"])
    n_expert = expert_layers(sizes)
    attention_per_token = flops_axk1.attention_projection_flops_per_token(sizes) \
        + indexer_projection_flops_per_token(sizes)
    pairs = index_score_flops_per_layer(sizes, L) + sparse_core_flops_per_layer(sizes, L)
    dense = 2.0 * 3 * d * int(sizes["intermediate_size"])
    published = int(flops_axk1._published(sizes, "n_routed_experts"))
    choices = int(sizes["num_experts_per_tok"]) * int(sizes["n_routed_experts"]) / published
    moe = 2.0 * d * published + (choices + int(sizes["n_shared_experts"])) * expert_flops_per_row(sizes)
    per_token = attention_layers(sizes) * attention_per_token \
        + (depth + mtp - n_expert) * dense + n_expert * moe + mtp * 2.0 * 2 * d * d
    return float(L * per_token + attention_layers(sizes) * pairs
                 + (1 + mtp) * 2.0 * n_positions * d * int(sizes["vocab_size"]))
