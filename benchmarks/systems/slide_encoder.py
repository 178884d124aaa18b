"""The slide stage as ``pipeline.run_inference_with_slide_encoder`` runs it:
the jitted function ``pipeline.slide_forward_fn`` returns, built once (the
entry builds it anew on every call and so retraces; PERF.md), with that
entry's conversions around it."""

from __future__ import annotations

import numpy as np

from benchmarks.lib import flops, reference


class System:
    unit = "tokens"

    def __init__(self, config: dict, tiny: bool):
        import jax.numpy as jnp

        from gigapath_tpu import pipeline
        from gigapath_tpu.models import longnet_config
        from gigapath_tpu.utils.registry import create_model_from_registry
        import gigapath_tpu.models.slide_encoder as se

        self.sizes = config["tiny"] if tiny else config
        self.model = create_model_from_registry(
            self.sizes["arch"], in_chans=int(self.sizes["in_chans"]),
            global_pool=False, dtype=jnp.bfloat16,
        )
        enc = longnet_config.get_config(self.model.encoder_name)
        built = {
            "embed_dim": self.model.embed_dim, "depth": self.model.depth,
            "mlp_ratio": self.model.mlp_ratio, "norm_eps": self.model.norm_eps,
            "tile_size": self.model.tile_size, "slide_ngrids": self.model.slide_ngrids,
            "num_heads": enc["encoder_attention_heads"],
            "dilated_ratio": _ints(self.model.dilated_ratio),
            "segments": list(self.model.segment_length
                             or se.get_optimal_segment_length(self.model.max_wsi_size,
                                                              self.model.tile_size)),
        }
        stated = dict(self.sizes, segments=list(reference.segment_schedule(self.sizes)))
        for key, value in built.items():
            if stated[key] != value:
                raise ValueError(
                    f"{self.sizes['arch']}: the program builds {key}={value!r}, "
                    f"the configuration file says {stated[key]!r}"
                )
        self._pipeline = pipeline

    def param_shapes(self):
        import jax
        import jax.numpy as jnp

        x = jax.ShapeDtypeStruct((1, 4, int(self.sizes["in_chans"])), jnp.float32)
        c = jax.ShapeDtypeStruct((1, 4, 2), jnp.float32)
        return jax.eval_shape(self.model.init, jax.random.PRNGKey(0), x, c)["params"]

    def make_fn(self):
        return self._pipeline.slide_forward_fn(self.model)

    def host_batch(self, rng, traffic):
        b, n = int(traffic["batch"]), int(traffic["tokens"])
        feats = rng.standard_normal((b, n, int(self.sizes["in_chans"])), dtype=np.float32)
        coords = rng.uniform(0, float(traffic["coord_max"]), (b, n, 2)).astype(np.float32)
        return feats, coords

    def to_device(self, batch):
        import jax.numpy as jnp

        feats, coords = batch
        return (jnp.asarray(feats).astype(jnp.bfloat16), jnp.asarray(coords, jnp.float32))

    def to_host(self, out):
        # every layer's embedding, [B, depth + 1, E]
        return np.stack([np.asarray(e, np.float32) for e in out], axis=1)

    def work(self, batch) -> int:
        return batch[0].shape[0] * batch[0].shape[1]

    def items(self, batch) -> list:
        return [batch[0].shape[1]] * batch[0].shape[0]

    def flops(self, batch) -> float:
        return sum(flops.slide_forward_flops(self.sizes, n) for n in self.items(batch))

    def rows(self, batch) -> int:
        return batch[0].shape[0]

    def reference(self, params, batch, rows, mode):
        feats, coords = batch
        return np.stack([
            reference.slide_forward(params, feats[r], coords[r], self.sizes, mode)
            for r in rows
        ])


def _ints(text) -> list:
    import ast

    return [int(x) for x in (ast.literal_eval(text) if isinstance(text, str) else text)]
