"""A.X-K1 behind the scoring forward ``pipeline.run_inference_with_lm`` runs:
``systems/lm.py``'s adapter (the entry's conversions, the ``received`` counter,
the traffic's ids and rows) with this model's factory, its operation counts
(``lib/flops_axk1.py``) and its plain reference (``lib/reference_axk1.py``)."""

from __future__ import annotations

import numpy as np

from benchmarks.lib import flops_axk1, reference_axk1
from benchmarks.systems import lm

# configuration key -> the program's field (models/axk1.AXK1Config)
_BUILT = ("hidden_size", "num_hidden_layers", "num_attention_heads", "q_lora_rank",
          "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
          "intermediate_size", "moe_intermediate_size", "num_experts_per_tok", "n_group",
          "topk_group", "routed_scaling_factor", "n_shared_experts", "first_k_dense_replace",
          "rope_theta", "rms_norm_eps", "vocab_size", "depth", "expert_offset")
_ROPE = ("factor", "original_max_position_embeddings", "beta_fast", "beta_slow", "mscale",
         "mscale_all_dim")


class System(lm.System):

    def __init__(self, config: dict, tiny: bool):
        from gigapath_tpu import pipeline
        from gigapath_tpu.utils.registry import create_model_from_registry
        import gigapath_tpu.models.axk1  # noqa: F401  (registers the archs)

        self.sizes = sizes = config["tiny"] if tiny else config
        self.model = create_model_from_registry(
            sizes["arch"], depth=int(sizes["depth"]), vocab_size=int(sizes["vocab_size"]),
            experts_held=int(sizes["n_routed_experts"]),
            expert_offset=int(sizes["expert_offset"]),
        )
        built = self.model.cfg
        stated = dict(sizes, experts_held=sizes["n_routed_experts"],
                      n_routed_experts=sizes["published"]["n_routed_experts"],
                      **{"rope_" + key: sizes["rope_scaling"][key] for key in _ROPE})
        for key in _BUILT + ("experts_held", "n_routed_experts") + tuple("rope_" + k for k in _ROPE):
            if getattr(built, key) != stated[key]:
                raise ValueError(
                    f"{sizes['arch']}: the program builds {key}={getattr(built, key)!r}, "
                    f"the configuration file says {stated[key]!r}")
        self._pipeline = pipeline
        self.received = []

    def flops(self, batch) -> float:
        ids, positions = batch
        return ids.shape[0] * flops_axk1.lm_forward_flops(
            self.sizes, ids.shape[1], positions.shape[1])

    def reference(self, params, batch, rows, mode):
        ids, positions = batch
        p = positions.shape[1]
        out = np.empty((len(rows), int(self.sizes["vocab_size"])), np.float32)
        for b in sorted({int(r) // p for r in rows}):  # one forward a sequence
            mine = [i for i, r in enumerate(rows) if int(r) // p == b]
            out[mine] = reference_axk1.lm_forward(
                params, ids[b], positions[b][[int(rows[i]) % p for i in mine]],
                self.sizes, mode)
        return out
