"""DeepSeek-V3.2 behind the scoring forward ``pipeline.run_inference_with_lm``
runs: ``systems/lm.py``'s adapter (the entry's conversions, the traffic's ids
and rows) with this model's factory, its operation counts
(``lib/flops_deepseek_v32.py``) and its plain reference
(``lib/reference_deepseek_v32.py``). Two counters of the program ride here, one
array a request each: ``received`` (the tokens each held expert received,
``[expert layers, experts_held]``) and ``selected`` (the (query, key) pairs
each layer's selection handed its core for each sequence, ``[layers, B]``, from
the model's third output); where the share runs the prediction module its logits are kept in
``mtp_logits``.

The one conversion of the weights that is this file's: ``lib/weights_lm.py``
draws ``e_score_correction_bias`` 0.5 normal, as any leaf it has no rule for,
and the adapter hands program and reference that tree with those leaves
multiplied by ``BIAS_SCALE`` (the configuration's ``assumed`` says why)."""

from __future__ import annotations

import numpy as np

from benchmarks.lib import flops_deepseek_v32, reference_deepseek_v32
from benchmarks.systems import lm

# configuration key -> the program's field (models/deepseek_v32.DeepseekV32Config)
_BUILT = ("hidden_size", "num_hidden_layers", "num_attention_heads", "q_lora_rank",
          "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
          "intermediate_size", "moe_intermediate_size", "num_experts_per_tok", "n_group",
          "topk_group", "routed_scaling_factor", "n_shared_experts", "first_k_dense_replace",
          "rope_theta", "rms_norm_eps", "vocab_size", "depth", "expert_offset",
          "index_n_heads", "index_head_dim", "index_topk")
_ROPE = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale",
         "mscale_all_dim")
BIAS_SCALE = 0.04


def scaled_bias(params, made=None):
    """The tree with every expert layer's ``e_score_correction_bias`` times
    ``BIAS_SCALE``; every other leaf is the one given, not a copy. ``made``
    (layer name -> (the leaf given, the leaf scaled)) spares the product where
    the same leaf comes again."""
    made = {} if made is None else made
    out = dict(params)
    for name, layer in params.items():
        if isinstance(layer, dict) and "e_score_correction_bias" in layer.get("moe", {}):
            raw = layer["moe"]["e_score_correction_bias"]
            if made.get(name, (None,))[0] is not raw:
                made[name] = (raw, raw * BIAS_SCALE)
            out[name] = dict(layer, moe=dict(layer["moe"], e_score_correction_bias=made[name][1]))
    return out


class System(lm.System):

    def __init__(self, config: dict, tiny: bool):
        from gigapath_tpu import pipeline
        from gigapath_tpu.utils.registry import create_model_from_registry
        import gigapath_tpu.models.deepseek_v32  # noqa: F401  (registers the archs)

        self.sizes = sizes = config["tiny"] if tiny else config
        self.model = create_model_from_registry(
            sizes["arch"], depth=int(sizes["depth"]), vocab_size=int(sizes["vocab_size"]),
            experts_held=int(sizes["n_routed_experts"]),
            expert_offset=int(sizes["expert_offset"]),
            first_k_dense_replace=int(sizes["first_k_dense_replace"]),
            mtp=int(sizes["num_nextn_predict_layers"]),
        )
        built = self.model.cfg
        stated = dict(sizes, experts_held=sizes["n_routed_experts"],
                      mtp=sizes["num_nextn_predict_layers"],
                      **{key: sizes["published"][key]
                         for key in ("n_routed_experts", "num_nextn_predict_layers")},
                      **{"rope_" + key: sizes["rope_scaling"][key] for key in _ROPE})
        for key in _BUILT + ("experts_held", "n_routed_experts", "mtp", "num_nextn_predict_layers") \
                + tuple("rope_" + k for k in _ROPE):
            if getattr(built, key) != stated[key]:
                raise ValueError(
                    f"{sizes['arch']}: the program builds {key}={getattr(built, key)!r}, "
                    f"the configuration file says {stated[key]!r}")
        shapes = self.param_shapes()
        for i in range(int(sizes["first_k_dense_replace"]), int(sizes["depth"])):
            bias = shapes[f"layers_{i}"]["moe"].get("e_score_correction_bias")
            if bias is None or bias.shape != (sizes["published"]["n_routed_experts"],):
                raise ValueError(f"{sizes['arch']}: layer {i}'s router has no selection bias "
                                 "over the published experts (topk_method noaux_tc)")
        self._pipeline = pipeline
        # the small leaves only: a tree kept here would keep a seed's weights on the device
        self._bias_made = {}
        self.received, self.selected, self.mtp_logits = [], [], []

    def _params(self, params):
        return scaled_bias(params, self._bias_made)

    def make_fn(self):
        fn = self._pipeline.lm_forward_fn(self.model)
        return lambda params, ids, positions: fn(self._params(params), ids, positions)

    def to_host(self, out):
        logits, received, extras = out
        self.selected.append(np.asarray(extras["selected_pairs"]))
        if "mtp_logits" in extras:
            self.mtp_logits.append(np.asarray(extras["mtp_logits"], np.float32))
        return super().to_host((logits, received))

    def flops(self, batch) -> float:
        ids, positions = batch
        return ids.shape[0] * flops_deepseek_v32.lm_forward_flops(
            self.sizes, ids.shape[1], positions.shape[1])

    def reference(self, params, batch, rows, mode):
        ids, positions = batch
        p = positions.shape[1]
        params = self._params(params)
        out = np.empty((len(rows), int(self.sizes["vocab_size"])), np.float32)
        for b in sorted({int(r) // p for r in rows}):  # one forward a sequence
            mine = [i for i, r in enumerate(rows) if int(r) // p == b]
            out[mine] = reference_deepseek_v32.lm_forward(
                params, ids[b], positions[b][[int(rows[i]) % p for i in mine]],
                self.sizes, mode)
        return out
