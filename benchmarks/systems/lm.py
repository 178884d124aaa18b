"""Every language model behind the scoring forward as
``pipeline.run_inference_with_lm`` runs it: the jitted function
``pipeline.lm_forward_fn`` returns, built once, with that entry's conversions
around it (int32 ids and positions to the device, float32 logits back).

What differs by model is data in the configuration file, read here and
nowhere branched on:

- ``share``: the factory's share arguments, each to the configuration key that
  gives its value (``{"experts_held": "n_routed_experts", ...}``); a key the
  file does not name is not passed. Where the two names differ, the program's
  field under the key's own name has to hold the published count
  (``published``) behind the share.
- ``built``: the keys whose values the program has to build under the same
  name, or ``[field, "group.key"]`` for a value inside a group
  (``["rope_factor", "rope_scaling.factor"]``); ``built_over_depth``: list
  keys compared over the share's layers; ``supported``: ``{key: [the one
  value the program has, why]}``; ``expert_layer_leaves``: ``{"a.b": [the
  published count that is the leaf's length, why]}``, in every expert layer
  held (``first_k_dense_replace`` on). Each is checked once the program is
  built, and a difference is an error.
- ``model_module``: the program's module that registers the ``arch``;
  ``reference`` / ``flops``: ``"<module of lib/>.<function>"``, with the
  signatures ``lm_forward(params, ids, positions, sizes, mode)`` and
  ``lm_forward_flops(sizes, L, P)``.

The program's outputs after the logits are kept by name, one array a request
in the order fetched (the warm-up's first): ``kept["received"]`` (the tokens
each held expert received, ``[expert layers, experts_held]``) and every key of
a third output dict (``selected_pairs``, ``mtp_logits``). The readers of
``layer_metrics/`` take them from ``kept``."""

from __future__ import annotations

import collections
import importlib

import numpy as np


def _lib_function(name: str):
    """``"reference_lm.lm_forward"`` -> ``benchmarks.lib.reference_lm.lm_forward``."""
    module, function = name.rsplit(".", 1)
    return getattr(importlib.import_module("benchmarks.lib." + module), function)


def _at(tree, path: str):
    """The entry ``"a.b"`` names in nested dicts; None where there is none."""
    for key in path.split("."):
        tree = tree.get(key) if hasattr(tree, "get") else None
    return tree


class System:
    unit = "tokens"

    def __init__(self, config: dict, tiny: bool):
        from gigapath_tpu import pipeline
        from gigapath_tpu.utils.registry import create_model_from_registry

        importlib.import_module(config["model_module"])  # registers the archs
        self.sizes = sizes = config["tiny"] if tiny else config
        share = {arg: int(sizes[key]) for arg, key in config["share"].items()}
        self.model = create_model_from_registry(sizes["arch"], **share)
        self._reference = _lib_function(config["reference"])
        self._flops = _lib_function(config["flops"])
        self._check_built(config, sizes)
        self._pipeline = pipeline
        self.kept = collections.defaultdict(list)

    def _check_built(self, config: dict, sizes: dict):
        built, arch = self.model.cfg, sizes["arch"]
        pairs = [(arg, sizes[key]) for arg, key in config["share"].items()]
        pairs += [(key, sizes["published"][key]) for arg, key in config["share"].items()
                  if arg != key]
        pairs += [(entry, sizes[entry]) if isinstance(entry, str)
                  else (entry[0], _at(sizes, entry[1])) for entry in config["built"]]
        for field, stated in pairs:
            if getattr(built, field) != stated:
                raise ValueError(f"{arch}: the program builds {field}={getattr(built, field)!r}, "
                                 f"the configuration file says {stated!r}")
        depth = int(sizes["depth"])
        for key in config.get("built_over_depth", ()):
            if list(getattr(built, key)[:depth]) != list(sizes[key][:depth]):
                raise ValueError(f"{arch}: {key} differ from the configuration file's")
        for key, (value, why) in config.get("supported", {}).items():
            if sizes[key] != value:
                raise ValueError(f"{arch}: {key}={sizes[key]!r}, and {why}")
        leaves = config.get("expert_layer_leaves", {})
        shapes = self.param_shapes() if leaves else None
        for i in range(int(sizes.get("first_k_dense_replace", 0)), depth):
            for path, (count, why) in leaves.items():
                leaf = _at(shapes, f"layers_{i}.{path}")
                if getattr(leaf, "shape", None) != (sizes["published"][count],):
                    raise ValueError(f"{arch}: layer {i} has no {path} of {count} entries: {why}")

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
        logits, received, *more = out
        self.kept["received"].append(np.asarray(received))
        for extras in more:
            for name, value in extras.items():
                self.kept[name].append(np.asarray(value))
        logits = np.asarray(logits, np.float32)
        return logits.reshape(-1, logits.shape[-1])  # [B * P, vocab], a row an answer

    def work(self, batch) -> int:
        return batch[0].size

    def items(self, batch) -> list:
        return [batch[0].shape[1]] * batch[0].shape[0]

    def flops(self, batch) -> float:
        ids, positions = batch
        return ids.shape[0] * self._flops(self.sizes, ids.shape[1], positions.shape[1])

    def rows(self, batch) -> int:
        return batch[1].size

    def reference(self, params, batch, rows, mode):
        ids, positions = batch
        p = positions.shape[1]
        out = np.empty((len(rows), int(self.sizes["vocab_size"])), np.float32)
        for b in sorted({int(r) // p for r in rows}):  # one forward a sequence
            mine = [i for i, r in enumerate(rows) if int(r) // p == b]
            out[mine] = self._reference(
                params, ids[b], positions[b][[int(rows[i]) % p for i in mine]],
                self.sizes, mode)
        return out
