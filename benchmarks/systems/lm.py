"""The scoring forward as ``pipeline.run_inference_with_lm`` runs it: the
jitted function ``pipeline.lm_forward_fn`` returns, built once, with that
entry's conversions around it (int32 ids and positions to the device, float32
logits back). The program's second answer, the tokens each held expert
received, rides here (``received``, one ``[layers, experts_held]`` array a
request fetched) because the driver wants one array from ``to_host``."""

from __future__ import annotations

import numpy as np

from benchmarks.lib import flops_lm, reference_lm

# configuration key -> the program's field (models/granite_hybrid.GraniteHybridConfig)
_BUILT = ("hidden_size", "num_attention_heads", "num_key_value_heads", "intermediate_size",
          "shared_intermediate_size", "num_experts_per_tok", "mamba_n_heads", "mamba_d_head",
          "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
          "attention_multiplier", "embedding_multiplier", "logits_scaling",
          "residual_multiplier", "rms_norm_eps", "vocab_size", "depth", "expert_offset")


class System:
    unit = "tokens"

    def __init__(self, config: dict, tiny: bool):
        from gigapath_tpu import pipeline
        from gigapath_tpu.utils.registry import create_model_from_registry
        import gigapath_tpu.models.granite_hybrid  # noqa: F401  (registers the archs)

        self.sizes = sizes = config["tiny"] if tiny else config
        self.model = create_model_from_registry(
            sizes["arch"], depth=int(sizes["depth"]), vocab_size=int(sizes["vocab_size"]),
            experts_held=int(sizes["num_local_experts"]),
            expert_offset=int(sizes["expert_offset"]),
        )
        built = self.model.cfg
        stated = dict(sizes, experts_held=sizes["num_local_experts"],
                      num_local_experts=sizes["published"]["num_local_experts"])
        for key in _BUILT + ("experts_held", "num_local_experts"):
            if getattr(built, key) != stated[key]:
                raise ValueError(
                    f"{sizes['arch']}: the program builds {key}={getattr(built, key)!r}, "
                    f"the configuration file says {stated[key]!r}")
        if sizes["mamba_n_groups"] != 1:
            raise ValueError(f"{sizes['arch']}: the program shares B and C over all heads (one group)")
        if list(built.layer_types[: built.depth]) != list(sizes["layer_types"][: built.depth]):
            raise ValueError(f"{sizes['arch']}: layer_types differ from the configuration file's")
        self._pipeline = pipeline
        self.received = []

    def param_shapes(self):
        import jax
        import jax.numpy as jnp

        ids = jax.ShapeDtypeStruct((1, 4), jnp.int32)
        return jax.eval_shape(self.model.init, jax.random.PRNGKey(0), ids, ids)["params"]

    def make_fn(self):
        return self._pipeline.lm_forward_fn(self.model)

    def host_batch(self, rng, traffic):
        b, n, p = int(traffic["batch"]), int(traffic["tokens"]), int(traffic["positions"])
        ids = rng.integers(0, int(self.sizes["vocab_size"]), (b, n), dtype=np.int32)
        # the last position and p - 1 more, distinct, in rising order
        others = np.stack([rng.permutation(n - 1)[: p - 1] for _ in range(b)])
        positions = np.sort(np.concatenate([others, np.full((b, 1), n - 1)], axis=1), axis=1)
        return ids, positions.astype(np.int32)

    def to_device(self, batch):
        import jax.numpy as jnp

        return tuple(jnp.asarray(a, jnp.int32) for a in batch)

    def to_host(self, out):
        logits, received = out
        self.received.append(np.asarray(received))
        logits = np.asarray(logits, np.float32)
        return logits.reshape(-1, logits.shape[-1])  # [B * P, vocab], a row an answer

    def work(self, batch) -> int:
        return batch[0].size

    def items(self, batch) -> list:
        return [batch[0].shape[1]] * batch[0].shape[0]

    def flops(self, batch) -> float:
        ids, positions = batch
        return ids.shape[0] * flops_lm.lm_forward_flops(
            self.sizes, ids.shape[1], positions.shape[1])

    def rows(self, batch) -> int:
        return batch[1].size

    def reference(self, params, batch, rows, mode):
        ids, positions = batch
        p = positions.shape[1]
        out = np.empty((len(rows), int(self.sizes["vocab_size"])), np.float32)
        for b in sorted({int(r) // p for r in rows}):  # one forward a sequence
            mine = [i for i, r in enumerate(rows) if int(r) // p == b]
            out[mine] = reference_lm.lm_forward(
                params, ids[b], positions[b][[int(rows[i]) % p for i in mine]],
                self.sizes, mode)
        return out
