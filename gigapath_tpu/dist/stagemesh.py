"""Per-stage meshes and the declarative sharding-rule registry.

The disaggregated pipeline runs the two encoders as separate fleets, and
each fleet needs its own mesh geometry and parameter layout:

- the **tile encoder** is data-parallel over tiles (every device crunches
  its own tile batch; optional tensor parallelism over the ViT's hidden
  dim) — axes ``("data", "model")``;
- the **slide encoder** is sequence/model-sharded (the 10^5-10^6-token
  tile-embedding sequence is what must split) — axes
  ``("data", "seq", "model")``.

Instead of hand-wiring pjit in_shardings per call site, each stage's
layout is a *registry entry*: an ordered list of
``(param-path regex, PartitionSpec)`` rules resolved against the param
tree by :func:`match_partition_rules` (the pattern of SNIPPETS.md [1] —
first matching rule wins, scalars never partition, an uncovered param is
a loud error, not silent replication). Both fleets consume the same
registry, so "what crosses which axis" stays auditable in one place —
the same philosophy as ``parallel/sharding.py``'s ``_SEQ_COLLECTIVES``
table, lifted from collectives to layouts.

Mesh construction delegates to :func:`gigapath_tpu.parallel.mesh.make_mesh`
over each stage's axis subset; rules degrade gracefully when a mesh
lacks (or has size 1 on) an axis a spec names.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from gigapath_tpu.parallel.mesh import make_mesh
from gigapath_tpu.parallel.sharding import _COLUMN_PARALLEL, _ROW_PARALLEL


def match_partition_rules(rules: Sequence[Tuple[str, P]], params):
    """PartitionSpec pytree from ordered ``(regex, spec)`` rules.

    Each leaf's ``/``-joined module path (``encoder/layers_0/fc1/kernel``)
    is matched with ``re.search``; the FIRST matching rule wins. Scalar
    (or 1-element) leaves never partition. A leaf matching no rule
    raises — a silent fall-through to replicated is exactly the bug
    class gigalint GL003 exists for, so the registry ends every stage's
    list with an explicit catch-all instead.
    """
    compiled = [(re.compile(rule), spec) for rule, spec in rules]

    def one(path, leaf):
        name = "/".join(str(getattr(p, "key", p)) for p in path)
        shape = getattr(leaf, "shape", ())
        if len(shape) == 0 or int(np.prod(shape)) == 1:
            return P()
        for rx, spec in compiled:
            if rx.search(name) is not None:
                return spec
        raise ValueError(
            f"no partition rule matches param '{name}' "
            f"(shape {tuple(shape)}); add a rule (or an explicit "
            "catch-all) to the stage's registry entry"
        )

    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    return jax.tree_util.tree_unflatten(
        treedef, [one(path, leaf) for path, leaf in flat]
    )


@dataclasses.dataclass(frozen=True)
class StageSpec:
    """One fleet's declarative geometry + layout."""

    name: str
    axes: Tuple[str, ...]
    rules: Tuple[Tuple[str, P], ...]
    description: str = ""


def _tp_rules(model_axis: str = "model") -> Tuple[Tuple[str, P], ...]:
    """The tensor-parallel kernel rules, derived from the SAME
    column/row-parallel name lists ``parallel/sharding.py`` maintains
    (and gigalint GL003 audits) — two spellings of one layout table, by
    construction."""
    col = "|".join(_COLUMN_PARALLEL)
    row = "|".join(_ROW_PARALLEL)
    return (
        (rf"(^|/)({col})/kernel$", P(None, model_axis)),
        (rf"(^|/)({row})/kernel$", P(model_axis, None)),
        # vmapped MoE experts carry a leading E axis (ops/moe/moe_layer)
        (r"(^|/)experts/", P("expert")),
        (r".*", P()),  # everything else (biases, norms, embeddings)
    )


_REGISTRY: Dict[str, StageSpec] = {}


def register_stage(spec: StageSpec) -> StageSpec:
    _REGISTRY[spec.name] = spec
    return spec


def get_stage(name: str) -> StageSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown stage '{name}' (registered: {stage_names()})"
        ) from None


def stage_names() -> List[str]:
    return sorted(_REGISTRY)


register_stage(StageSpec(
    name="tile_encoder",
    axes=("data", "model"),
    rules=_tp_rules(),
    description="ViT-G tile fleet: data-parallel over tiles, optional "
                "tensor parallelism over hidden/head dims",
))

register_stage(StageSpec(
    name="slide_encoder",
    axes=("data", "seq", "model"),
    rules=_tp_rules(),
    description="LongNet slide fleet: the tile-embedding sequence shards "
                "over seq (ring/chunked prefill), kernels over model",
))


def stage_process_devices() -> list:
    """The one device a dist stage process computes on.

    Each stage (tile worker, slide consumer) is its own OS process, and an
    accelerator belongs to one process at a time: on a host with a single
    chip the second stage process to start cannot get it. That is reported
    here, by cause, instead of as whatever the backend raises — the
    two-process layout needs a device per process (or
    ``JAX_PLATFORMS=cpu`` in the stages that are to stay off the chip)."""
    import jax

    try:
        return jax.devices()[:1]
    except RuntimeError as e:
        raise RuntimeError(
            "dist stage process could not initialise its JAX backend. The "
            "two-process layout (gigapath_tpu.dist.worker / "
            "gigapath_tpu.dist.pipeline) needs a device per process: an "
            "accelerator belongs to ONE process at a time, so on a one-chip "
            "host a second stage process cannot take it. Give each stage "
            "process its own device, or set JAX_PLATFORMS=cpu for the "
            f"stages that stay off the chip. Backend error: {e}"
        ) from e


def stage_mesh(name: str, n_devices: Optional[int] = None, *,
               devices=None,
               axis_sizes: Optional[Dict[str, int]] = None) -> Mesh:
    """Build one stage's mesh over (a subset of) the visible devices —
    the two-process-group dryrun gives each stage its own device slice
    via ``devices=``."""
    spec = get_stage(name)
    if axis_sizes is not None:
        unknown = set(axis_sizes) - set(spec.axes)
        if unknown:
            raise ValueError(
                f"stage '{name}' has axes {spec.axes}; axis_sizes names "
                f"{sorted(unknown)}"
            )
    return make_mesh(n_devices, axes=spec.axes, devices=devices,
                     axis_sizes=axis_sizes)


def _degrade(spec: P, mesh: Mesh) -> P:
    """Drop axis names the mesh lacks (or has size 1 on) from a spec —
    the rules stay declarative, the mesh decides what is real."""
    live = {a for a in mesh.axis_names if mesh.shape[a] > 1}

    def keep(entry):
        if entry is None:
            return None
        if isinstance(entry, (tuple, list)):
            kept = tuple(e for e in entry if e in live)
            return kept if kept else None
        return entry if entry in live else None

    return P(*(keep(e) for e in spec))


def stage_param_shardings(name: str, params, mesh: Mesh):
    """NamedSharding pytree for one stage's params under its mesh (the
    registry rules, degraded to the mesh's live axes)."""
    spec = get_stage(name)
    specs = match_partition_rules(spec.rules, params)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, _degrade(s, mesh)), specs,
        is_leaf=lambda x: isinstance(x, P),
    )
