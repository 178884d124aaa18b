"""The tile stage as ``pipeline.run_inference_with_tile_encoder`` runs it:
the jitted function ``pipeline.tile_encode_fn`` returns, with that entry's
per-batch conversions around it."""

from __future__ import annotations

import numpy as np

from benchmarks.lib import flops, reference


class System:
    unit = "tiles"

    def __init__(self, config: dict, tiny: bool):
        import jax.numpy as jnp

        from gigapath_tpu import pipeline
        from gigapath_tpu.utils.registry import create_model_from_registry
        import gigapath_tpu.models.tile_encoder  # noqa: F401  (registers the archs)

        self.sizes = config["tiny"] if tiny else config
        self.model = create_model_from_registry(self.sizes["arch"], dtype=jnp.bfloat16)
        for key in ("img_size", "patch_size", "embed_dim", "depth", "num_heads",
                    "mlp_ratio", "norm_eps", "init_values"):
            if getattr(self.model, key) != self.sizes[key]:
                raise ValueError(
                    f"{self.sizes['arch']}: the program builds {key}="
                    f"{getattr(self.model, key)!r}, the configuration file "
                    f"says {self.sizes[key]!r}"
                )
        self._pipeline = pipeline

    def param_shapes(self):
        import jax
        import jax.numpy as jnp

        s = int(self.sizes["img_size"])
        x = jax.ShapeDtypeStruct((1, s, s, 3), jnp.float32)
        return jax.eval_shape(self.model.init, jax.random.PRNGKey(0), x)["params"]

    def make_fn(self):
        return self._pipeline.tile_encode_fn(self.model)

    def host_batch(self, rng, traffic):
        s = int(self.sizes["img_size"])
        return rng.standard_normal((int(traffic["batch"]), s, s, 3), dtype=np.float32)

    def to_device(self, batch):
        import jax.numpy as jnp

        return (jnp.asarray(batch, jnp.bfloat16),)

    def to_host(self, out):
        return np.asarray(out, np.float32)

    def work(self, batch) -> int:
        return batch.shape[0]

    def items(self, batch) -> list:
        return [1] * batch.shape[0]

    def flops(self, batch) -> float:
        return batch.shape[0] * flops.tile_forward_flops(self.sizes)

    def rows(self, batch) -> int:
        return batch.shape[0]

    def reference(self, params, batch, rows, mode):
        return reference.vit_forward(params, batch[rows], self.sizes, mode)
