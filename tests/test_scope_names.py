"""The names the program gives its own work on the device (PERF.md §3):
``jax.named_scope`` in both encoders and the attention ops, ``name=`` on
every ``pl.pallas_call``, and named jitted steps. Each name a path can reach
has to stand in that path's lowered text, one case per name, so a refactor
that drops one fails here; and the scopes add no equation to either forward.

Lowering only: nothing here runs a kernel. The Pallas paths lower in
interpret mode (the library never picks it: the tests ask for it)."""

import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmarks.lib import tables

_TILE = tables.load("configs", "gigapath_tile_enc")["tiny"]
_SLIDE = tables.load("configs", "gigapath_slide_enc12l768d")["tiny"]
_LM = tables.load("configs", "granite4h_small_ep2")["tiny"]
_AXK1 = tables.load("configs", "axk1_ep16")["tiny"]
_N_TOKENS = 40  # + class token = 41: three 16-token and two 32-token segments


def _tile_forward():
    from gigapath_tpu import pipeline
    from gigapath_tpu.utils.registry import create_model_from_registry
    import gigapath_tpu.models.tile_encoder  # noqa: F401  (registers the archs)

    model = create_model_from_registry(_TILE["arch"], dtype=jnp.bfloat16)
    s = _TILE["img_size"]
    x = jax.ShapeDtypeStruct((2, s, s, 3), jnp.bfloat16)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)["params"]
    # a function of its own: the entry's cached one may hold a trace made under the other gate
    return pipeline.tile_encode_fn.__wrapped__(model), (params, x)


def _tile_forward_heads_of_64():
    """A two-block ViT whose heads fill half a lane group, as ViT-G/14's do:
    with the device gate on, its attention core is the packed-QKV kernel."""
    from gigapath_tpu import pipeline
    from gigapath_tpu.models.tile_encoder import VisionTransformer

    model = VisionTransformer(img_size=32, patch_size=16, embed_dim=128, depth=2,
                              num_heads=2, mlp_ratio=2.0, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, 32, 32, 3), jnp.bfloat16)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)["params"]
    return pipeline.tile_encode_fn.__wrapped__(model), (params, x)


def _slide_forward():
    from gigapath_tpu import pipeline
    from gigapath_tpu.utils.registry import create_model_from_registry
    import gigapath_tpu.models.slide_encoder  # noqa: F401

    model = create_model_from_registry(
        _SLIDE["arch"], in_chans=_SLIDE["in_chans"], global_pool=False,
        dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((2, _N_TOKENS, _SLIDE["in_chans"]), jnp.bfloat16)
    c = jax.ShapeDtypeStruct((2, _N_TOKENS, 2), jnp.float32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), x, c)["params"]
    return pipeline.slide_forward_fn.__wrapped__(model), (params, x, c)


def _lm_forward(length=40, **widths):
    from gigapath_tpu import pipeline
    from gigapath_tpu.utils.registry import create_model_from_registry
    import gigapath_tpu.models.granite_hybrid  # noqa: F401

    model = create_model_from_registry(
        _LM["arch"], depth=_LM["depth"], vocab_size=_LM["vocab_size"],
        experts_held=_LM["num_local_experts"], expert_offset=_LM["expert_offset"], **widths)
    ids = jax.ShapeDtypeStruct((2, length), jnp.int32)
    rows = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, rows)["params"]
    # a function of its own: the entry's cached one may hold a trace made under the other gate
    return pipeline.lm_forward_fn.__wrapped__(model), (params, ids, rows)


def _axk1_forward(length=40, **widths):
    from gigapath_tpu import pipeline
    from gigapath_tpu.utils.registry import create_model_from_registry
    import gigapath_tpu.models.axk1  # noqa: F401

    model = create_model_from_registry(
        _AXK1["arch"], depth=_AXK1["depth"], vocab_size=_AXK1["vocab_size"],
        experts_held=_AXK1["n_routed_experts"], expert_offset=_AXK1["expert_offset"], **widths)
    ids = jax.ShapeDtypeStruct((2, length), jnp.int32)
    rows = jax.ShapeDtypeStruct((2, 4), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, rows)["params"]
    return pipeline.lm_forward_fn.__wrapped__(model), (params, ids, rows)


def _on_kernels(monkeypatch_context, build):
    """``build()`` with the device gate answering "TPU" and every
    ``pallas_call`` in interpret mode: the slide encoder then takes the fused
    phase-major path, as it does on the chip."""
    import gigapath_tpu.ops.flash_attention as fa

    monkeypatch_context.setattr(fa, "_on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        return build()


def _qkv(L=64, H=4, Dh=8, B=1):
    return [jax.ShapeDtypeStruct((B, L, H, Dh), jnp.float32)] * 3


def _grad_of(op):
    return jax.jit(jax.grad(lambda q, k, v: op(q, k, v).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2)))


def _text(fn, args) -> str:
    return fn.lower(*args).as_text(debug_info=True)


def _tpu_hlo(fn, args) -> str:
    """The program lowered for the TPU platform (no chip and no compile:
    each ``pallas_call`` becomes a ``tpu_custom_call`` with the result types
    the compiled program keeps) as HLO text, the form the benchmark's kernel
    tables read an operation in."""
    import gigapath_tpu.ops.flash_attention as fa

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "_on_tpu", lambda: True)
        lowered = fn.trace(*args).lower(lowering_platforms=("tpu",))
    from jax._src.lib import xla_client

    options = xla_client._xla.HloPrintOptions()
    options.print_metadata = True  # op_name holds the kernel's name
    options.print_backend_config = False  # the serialized kernel bodies
    return lowered.compiler_ir(dialect="hlo").as_hlo_module().to_string(options)


@functools.lru_cache(maxsize=None)
def _lowered(path: str) -> str:
    """The lowered text of one path through the program, made once."""
    from gigapath_tpu.ops import dilated_attention as da
    from gigapath_tpu.ops import pallas_dilated as pd

    with pytest.MonkeyPatch.context() as mp:
        if path == "tile":
            return _text(*_tile_forward())
        if path == "tile_kernels":
            return _on_kernels(mp, lambda: _text(*_tile_forward_heads_of_64()))
        if path == "slide_jnp":
            return _text(*_slide_forward())
        if path == "slide_kernels":
            return _on_kernels(mp, lambda: _text(*_slide_forward()))
        if path == "lm_jnp":
            return _text(*_lm_forward())
        if path == "lm_kernels":  # the expert layer's kernels and the causal grouped-KV core
            # the grouped product tiles widths that are multiples of 128 and no
            # other; the row kernels take a bfloat16 row of an even number of
            # 128-lane lines (ops/moe/pallas_rows.fits), so the hidden width is 256
            return _on_kernels(mp, lambda: _text(*_lm_forward(
                length=512, hidden_size=256, intermediate_size=128)))
        if path == "axk1_jnp":
            return _text(*_axk1_forward())
        if path == "axk1_kernels":  # widths the grouped product and the row kernels take, as lm_kernels
            return _on_kernels(mp, lambda: _text(*_axk1_forward(
                length=512, hidden_size=256, moe_intermediate_size=128)))
        if path == "fused_grad":
            return _text(_grad_of(functools.partial(
                da.dilated_attention_fused, segment_lengths=[16, 32],
                dilated_ratios=[1, 2], interpret=True)), _qkv())
        if path == "slide_tpu_hlo":
            return _tpu_hlo(*_slide_forward())
        if path == "fused_grad_tpu_hlo":
            return _tpu_hlo(_grad_of(functools.partial(
                da.dilated_attention_fused, segment_lengths=[16, 32],
                dilated_ratios=[1, 2])),
                [jax.ShapeDtypeStruct((1, 64, 4, 8), jnp.bfloat16)] * 3)
        if path == "fused_variants_grad":  # the kernel variants that are off by default
            flags = pd.snapshot_flags()._replace(pipelined_bwd=True)
            return _text(_grad_of(functools.partial(
                da.dilated_attention_fused, segment_lengths=[32, 64],
                dilated_ratios=[1, 2], interpret=True, flags=flags)), _qkv())
        if path == "fused_streaming":
            return _text(jax.jit(functools.partial(
                da.dilated_attention_fused, segment_lengths=[16, 32],
                dilated_ratios=[1, 2], interpret=True, streaming_fusion=True)), _qkv())
        if path == "head_major_grad":
            return _text(_grad_of(functools.partial(
                da.dilated_attention_bhld, segment_lengths=[16, 32],
                dilated_ratios=[1, 2], interpret=True, use_pallas=True)), _qkv())
        if path == "head_major_streaming":
            return _text(jax.jit(functools.partial(
                da.dilated_attention_bhld, segment_lengths=[16, 32],
                dilated_ratios=[1, 2], use_pallas=False, streaming_fusion=True)), _qkv())
        if path == "flash_grad":
            from gigapath_tpu.ops.pallas_flash import pallas_flash_attention

            return _text(_grad_of(lambda q, k, v: pallas_flash_attention(
                q, k, v, interpret=True)[0]), _qkv(L=128))
        if path == "stream_fold_grad":
            from gigapath_tpu.ops.pallas_dilated import snapshot_flags
            from gigapath_tpu.ops.streaming_prefill import fold_pair

            flags = snapshot_flags()._replace(fold_pallas=True)
            acc = jax.ShapeDtypeStruct((1, 64, 4, 8), jnp.float32)
            lse = jax.ShapeDtypeStruct((1, 4, 64), jnp.float32)

            def loss(q, k, v, acc, lse):
                out, _ = fold_pair(acc, lse, q, k, v, 0, 0, None,
                                   segment_len=32, ratio=2, flags=flags)
                return out.sum()

            with pltpu.force_tpu_interpret_mode():  # the fold takes no interpret=
                return _text(jax.jit(jax.grad(loss, argnums=(0, 1, 2))),
                             _qkv() + [acc, lse])
        if path == "quant":
            from gigapath_tpu.quant.qflash import q_flash_attention_pallas
            from gigapath_tpu.quant.qmatmul import q_matmul_pallas
            from gigapath_tpu.quant.qtensor import quantize_per_channel

            def both(q, k, v, x, w):
                out, _ = q_flash_attention_pallas(q, k, v, interpret=True)
                return out, q_matmul_pallas(x, quantize_per_channel(w), interpret=True)

            x = jax.ShapeDtypeStruct((128, 128), jnp.float32)
            return _text(jax.jit(both), _qkv(L=128) + [x, x])
    raise KeyError(path)


# path -> every name of PERF.md §3 that the path reaches
_NAMES = {
    "tile": ["jit_tile_encode", "attn_core"],
    "tile_kernels": ["jit_tile_encode", "attn_core", "kernel_fwd", "vit_attn_fwd"],
    "slide_jnp": ["jit_slide_forward", "dilated_attn", "branch_r1", "branch_r2", "pack",
                  "kernel_fwd", "unpack", "merge"],
    "slide_kernels": ["jit_slide_forward", "dilated_attn", "branch_r1", "branch_r2", "pack",
                      "kernel_fwd", "merge", "dilated_pack", "dilated_fwd_overlap",
                      "dilated_epilogue_fwd"],
    "lm_jnp": ["jit_lm_forward", "ssm_mixer", "in_proj", "conv", "ssd_scan", "gate_norm",
               "out_proj", "moe", "router", "dispatch", "experts", "kernel_fwd", "combine",
               "shared_mlp", "self_attn", "attn_core", "lm_head"],
    "lm_kernels": ["jit_lm_forward", "ssd_scan", "moe", "dispatch", "moe_dispatch", "experts",
                   "kernel_fwd", "gmm", "combine", "moe_combine", "attn_core",
                   "flash_fwd_overlap", "lm_head"],
    "axk1_jnp": ["jit_lm_forward", "self_attn", "q_a_proj", "q_a_layernorm", "q_b_proj",
                 "kv_a_proj_with_mqa", "kv_a_layernorm", "kv_b_proj", "rope", "attn_core",
                 "kernel_fwd", "o_proj", "mlp", "moe", "router", "dispatch", "experts",
                 "combine", "shared_experts", "lm_head"],
    "axk1_kernels": ["jit_lm_forward", "rope", "attn_core", "kernel_fwd", "flash_fwd_overlap",
                     "moe", "router", "dispatch", "moe_dispatch", "experts", "gmm", "combine",
                     "moe_combine", "shared_experts", "lm_head"],
    "fused_grad": ["dilated_attn", "branch_r2", "pack", "kernel_fwd", "kernel_dq",
                   "kernel_dkv", "unpack", "merge", "dilated_pack", "dilated_fwd_overlap",
                   "dilated_dq", "dilated_dkv", "dilated_unpack", "dilated_epilogue_fwd",
                   "dilated_epilogue_bwd"],
    "fused_variants_grad": ["dilated_attn", "branch_r2", "merge", "dilated_fwd_overlap",
                            "dilated_dq_pipe", "dilated_dkv_pipe", "dilated_pack",
                            "dilated_unpack", "dilated_epilogue_fwd",
                            "dilated_epilogue_bwd"],
    "fused_streaming": ["dilated_attn", "branch_r1", "branch_r2", "merge"],
    "head_major_grad": ["dilated_attn", "branch_r1", "branch_r2", "dilate", "kernel_fwd",
                        "kernel_dq", "kernel_dkv", "undilate", "merge", "flash_fwd",
                        "flash_dq", "flash_dkv"],
    "head_major_streaming": ["dilated_attn", "branch_r2", "dilate", "kernel_fwd", "undilate",
                             "merge"],
    "flash_grad": ["kernel_fwd", "kernel_dq", "kernel_dkv", "flash_fwd", "flash_dq",
                   "flash_dkv"],
    "stream_fold_grad": ["fold", "kernel_fwd", "kernel_dq", "kernel_dkv", "stream_fold",
                         "stream_fold_dq", "stream_fold_dkv"],
    "quant": ["kernel_fwd", "q_flash_fwd", "q_matmul"],
}


@pytest.mark.parametrize("path,name", [(p, n) for p, names in _NAMES.items() for n in names])
def test_name_stands_in_the_lowered_text(path, name):
    text = _lowered(path)
    if name.startswith("jit_"):  # the module on the trace's "XLA Modules" line
        assert re.search(rf"module @{name}\b", text)
        assert "jit__lambda" not in text
    else:  # a component of some operation's op_name path
        # (the path starts anew inside a function jitted on its own, as the
        # ViT's kernel is; the compiled program joins it to the caller's:
        # tests/test_tpu_compile.py holds that)
        assert re.search(rf'"(?:[^"]*[/(])?{name}[/)][^"]*"', text), name


def test_a_branch_holds_its_steps_in_order_of_the_path():
    """``.../dilated_attn/branch_r2/pack|kernel_fwd/<kernel name>/...`` and
    ``.../dilated_attn/branch_r2/jit(_fwd_call_overlap)`` (the forward kernel,
    whose own path ``kernel_fwd/dilated_fwd_overlap`` starts anew inside) and
    ``.../dilated_attn/merge/jit(_epilogue_call)``: the scope-keyed reduction
    (benchmarks/lib/scopes.py) matches on this. ``unpack`` is the dense
    fallback's and the backward's: the forward holds none."""
    text = _lowered("slide_kernels")
    branch = r'"[^"]*/layers_1/self_attn/self_attn\._attend/dilated_attn/branch_r2/'
    assert re.search(branch + "pack/dilated_pack/", text)
    assert re.search(branch + r'jit\(_fwd_call_overlap\)"', text)
    assert re.search(
        r'"[^"]*/layers_1/self_attn/self_attn\._attend/dilated_attn/merge/jit\(_epilogue_call\)"', text)
    # a kernel's own path starts anew inside its jitted function: one trace
    # and one lowering for all layers (a forward kernel a branch shape, of
    # which this tiny schedule has two)
    assert re.search(r'"kernel_fwd/dilated_fwd_overlap/', text)
    assert re.search(r'"dilated_epilogue_fwd/', text)
    assert len(re.findall(r"func.func private @_epilogue_call", text)) == 1
    assert len(re.findall(r"func.func private @_fwd_call_overlap", text)) == 2
    assert "dilated_unpack" not in text and "/unpack/" not in text
    backward = _lowered("fused_grad")
    for step, kernel in (("unpack", "dilated_unpack"), ("pack", "dilated_pack")):
        assert re.search(rf'"[^"]*branch_r2/{step}/{kernel}/', backward), step
    assert re.search(r'"[^"]*merge/jit\(_epilogue_bwd_call\)"', backward)
    assert re.search(r'"[^"]*/blocks_1/attn/attn_core/', _lowered("tile"))
    kernels = _lowered("tile_kernels")
    assert re.search(r'"[^"]*/blocks_1/attn/attn_core/jit\(packed_qkv_attention\)"', kernels)
    assert re.search(r'"kernel_fwd/vit_attn_fwd/', kernels)


def _picked(table: dict, operation: str) -> bool:
    """Whether the benchmark's own rule (``TraceReduction.kernel_seconds``)
    counts an operation of this HLO text under the kernel table."""
    from benchmarks.lib.trace import TraceReduction

    one = TraceReduction(window_s=1.0, busy_s=1.0, n_devices=1, op_total_s={operation: 1.0},
                         op_self_s={}, host_span_s={}, idle_gaps=[])
    return one.kernel_seconds(table) > 0


@pytest.mark.parametrize("path,epilogues", [
    ("slide_tpu_hlo", {"dilated_epilogue_fwd": 1}),  # one jitted function for both layers
    ("fused_grad_tpu_hlo", {"dilated_epilogue_fwd": 1, "dilated_epilogue_bwd": 2}),
], ids=["forward", "grad"])
def test_the_kernel_table_takes_the_epilogue_for_no_attention_kernel(path, epilogues):
    """``benchmarks/kernels/dilated_attn.json`` tells an attention kernel by
    what it returns, ``(bf16, f32)``. The merge epilogue returns one array
    in the forward and ``(f32, bf16)`` under differentiation, so of the
    program's custom calls the table picks the ``dilated_fwd*`` ones and no
    other: the epilogue's seconds are never added to the kernels'
    (``dilated_attn_roofline.slide``, ``attn_kernel_time_share.slide``)."""
    table = tables.kernel_table("dilated_attn")
    calls = [line for line in _lowered(path).splitlines() if " custom-call(" in line]
    kernel_of = lambda line: re.search(r'op_name="[^"]*?(\w+)/pallas_call"', line).group(1)
    picked = [kernel_of(line) for line in calls if _picked(table, line)]
    assert picked and set(picked) == {"dilated_fwd_overlap"}
    assert len(picked) == sum(kernel_of(line).startswith("dilated_fwd") for line in calls)
    for kernel, count in epilogues.items():
        assert sum(kernel_of(line) == kernel for line in calls) == count, kernel
    if path == "slide_tpu_hlo":
        assert not any(kernel_of(line) == "dilated_unpack" for line in calls)


def test_the_expert_layer_holds_its_steps_in_order_of_the_path():
    """``.../moe/dispatch|experts|combine/kernel_fwd/<kernel name>``: the row
    kernels stay under ``moe`` and outside ``experts`` (``moe_route`` of
    benchmarks/scopes/lm.json), the grouped products under ``experts``; no
    name of ours begins with ``gmm`` (benchmarks/kernels/moe_gmm_by_name.json
    matches on that)."""
    text = _lowered("lm_kernels")
    for step, kernel in (("dispatch", "jit(_dispatch_call)"), ("experts", "jit(gmm)"),
                         ("combine", "jit(_combine_call)")):
        assert f"/layers_1/moe/{step}/kernel_fwd/{kernel}" in text, step
    # a kernel's own path starts anew inside its jitted function (one trace
    # and one lowering for all layers); the compiled program joins the two
    for kernel in ("moe_dispatch", "moe_combine"):
        assert re.search(rf'"{kernel}/', text), kernel
    assert not re.search(r'"[^"]*/moe/experts/[^"]*moe_(dispatch|combine)', text)
    assert not re.search(r"gmm[\w]*(dispatch|combine)", text)


def test_latent_attention_holds_its_steps_in_order_of_the_path():
    """``.../layers_<i>/self_attn/<projection | rope | attn_core>/...`` and the
    expert layer's steps under ``moe`` (benchmarks/scopes/axk1.json matches on
    these); layer 0 has the dense ``mlp`` and no ``moe``, the later layers the
    reverse; every layer's core is one jitted function, so six layers share a
    lowering."""
    text = _lowered("axk1_kernels")
    for step in ("q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa", "kv_a_layernorm",
                 "kv_b_proj", "rope", "o_proj"):
        assert re.search(rf'"[^"]*/layers_2/self_attn/{step}[/"]', text), step
    assert "/layers_2/self_attn/attn_core/jit(_causal_core)" in text
    assert re.search(r'"kernel_fwd/flash_fwd_overlap/', text)
    assert len(re.findall(r"func.func private @_causal_core", text)) == 1
    assert re.search(r'"[^"]*/layers_0/mlp/', text) and not re.search(r'"[^"]*/layers_0/moe/', text)
    assert re.search(r'"[^"]*/layers_1/moe/router/', text)
    assert not re.search(r'"[^"]*/layers_1/mlp/', text)
    for step, kernel in (("dispatch", "jit(_dispatch_call)"), ("experts", "jit(gmm)"),
                         ("combine", "jit(_combine_call)")):
        assert f"/layers_1/moe/{step}/kernel_fwd/{kernel}" in text, step
    assert re.search(r'"[^"]*/layers_1/shared_experts/', text)
    assert re.search(r'"[^"]*/lm_head/lm_head/', text)


# Equation counts of the parent commit (7f80832), every nested jaxpr counted,
# written down once from that tree with ``_count``: scopes are metadata and
# add none, and neither a ``jit`` nor a ``custom_vjp_call``. ``slide_kernels``
# was counted again at PR 29 (1661 / 45 / 4 before): its copy kernels window
# and mask the dense array themselves, so their bodies hold more equations,
# and the pads, slices and reshapes around them are gone; and at PR 33
# (2077 / 39 / 4 before): the merge is one epilogue kernel over the packed
# results (a ``custom_vjp`` of its own around one jitted call a layer), the
# unpack kernels, the lse scatter and the XLA merge are gone from the forward;
# and at PR 35 (2143 / 35 / 6 before): the forward kernel's body holds four
# (two) chains a step where it held one, each with its own ``jnp.where`` and
# its own slices, inside one jitted call a branch.
_PARENT_EQUATIONS = {
    "tile": {"all": 245, "jit": 3, "custom_vjp_call": 0},
    "slide_jnp": {"all": 761, "jit": 17, "custom_vjp_call": 0},
    "slide_kernels": {"all": 3403, "jit": 139, "custom_vjp_call": 6},
}


def _count(jaxpr) -> dict:
    counts = {"all": 0, "jit": 0, "custom_vjp_call": 0}

    def walk(j):
        for eqn in j.eqns:
            counts["all"] += 1
            if eqn.primitive.name in counts:
                counts[eqn.primitive.name] += 1
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return counts


def _equations(path: str) -> dict:
    build = _tile_forward if path == "tile" else _slide_forward

    def make():
        fn, args = build()
        return _count(jax.make_jaxpr(fn)(*args))

    if path != "slide_kernels":
        return make()
    with pytest.MonkeyPatch.context() as mp:
        return _on_kernels(mp, make)


@pytest.mark.parametrize("path", sorted(_PARENT_EQUATIONS))
def test_scopes_add_no_equation(path):
    assert _equations(path) == _PARENT_EQUATIONS[path]


if __name__ == "__main__":  # prints the counts of whatever tree is on sys.path
    print({path: _equations(path) for path in sorted(_PARENT_EQUATIONS)})
