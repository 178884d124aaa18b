"""The dilated branches' pack / unpack copy kernels
(``ops/pallas_dilated._pack_phases`` / ``_unpack_phases``): they read and
write the dense [B, L, E] activation themselves, and are held here, bit for
bit, to a plain jnp pack / unpack written in this file: alone, through the
whole branch forward and backward (interpret mode), and in the lowered text
of the flagship schedule."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


def _jnp_pack(x, g, S, r, Mp, H, interpret=None):
    """Plain jnp [B, L, E] -> [B, S, r, hb, Mp, Dh]: packed row j of
    (segment s, phase p) is token s*g + j*r + p, heads p*hb .. (p+1)*hb - 1;
    rows past the segment or past L are zeros. Independent of the kernels:
    what they are held to, bit for bit."""
    B, L, E = x.shape
    hb, Dh = H // r, E // H
    x = jnp.pad(x, ((0, 0), (0, S * g - L), (0, 0))).reshape(B, S, g, E)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, Mp * r - g), (0, 0)))
    x = x.reshape(B, S, Mp, r, r, hb, Dh)  # [.., row, phase, band, head, :]
    diag = jnp.stack([x[:, :, :, p, p] for p in range(r)], axis=2)
    return diag.transpose(0, 1, 2, 4, 3, 5)


def _jnp_unpack(p6, L, E, g, S, r, interpret=None):
    """Plain jnp inverse of :func:`_jnp_pack`; off-band lanes are zeros."""
    B, _, _, hb, Mp, Dh = p6.shape
    x = jnp.zeros((B, S, Mp, r, r, hb, Dh), p6.dtype)
    for p in range(r):
        x = x.at[:, :, :, p, p].set(p6[:, :, p].transpose(0, 1, 3, 2, 4))
    x = x.reshape(B, S, Mp * r, E)[:, :, :g]
    return x.reshape(B, S * g, E)[:, :L]


def _branch_fwd_and_grads(q, k, v, sl, r, H, **kw):
    from gigapath_tpu.ops.pallas_dilated import dilated_branch_attention

    def loss(q_, k_, v_):
        o, _ = dilated_branch_attention(q_, k_, v_, sl, r, H, interpret=True, **kw)
        return (o * o).sum()

    o, lse = dilated_branch_attention(q, k, v, sl, r, H, interpret=True, **kw)
    return o, lse, jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


def _assert_branch_equals_jnp_copies(monkeypatch, q, k, v, sl, r, H, grads=True,
                                     **kw):
    """The branch with the Pallas copy kernels against the same branch with
    the plain jnp pack / unpack in their place (the attention kernels are
    the same on both sides): bit-identical out, lse and, with ``grads``,
    dq / dk / dv (the backward re-packs q, k, v, do and unpacks the three
    gradients through the same two functions)."""
    import gigapath_tpu.ops.pallas_dilated as pdm

    run = _branch_fwd_and_grads if grads else (
        lambda *a, **k_: pdm.dilated_branch_attention(*a, interpret=True, **k_) + ((),))
    o1, l1, g1 = run(q, k, v, sl, r, H, **kw)
    monkeypatch.setattr(pdm, "_pack_phases", _jnp_pack)
    monkeypatch.setattr(pdm, "_unpack_phases", _jnp_unpack)
    o0, l0, g0 = run(q, k, v, sl, r, H, **kw)
    monkeypatch.undo()
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o0))
    fin = np.asarray(l0) > -1e19
    np.testing.assert_array_equal(np.asarray(l1)[fin], np.asarray(l0)[fin])
    for a, b in zip(g1, g0):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.slow
@pytest.mark.parametrize(
    "L,sl,r,rl",
    [
        (300, 512, 2, 277),   # tail block straddles L; ragged real_len
        (523, 1024, 4, 523),  # L far from a bt*r multiple
        (260, 4096, 8, 201),  # hb == 1 band
    ],
)
def test_pack_direct_matches_padded(rng, monkeypatch, L, sl, r, rl):
    """The copy kernels read / write dense [B, L, E] directly, re-tiling
    in VMEM: bit-identical to a plain jnp pack / unpack of the zero-padded
    view, forward and backward."""
    H, Dh = 8, 16
    E = H * Dh
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, L, E)), jnp.float32) for _ in range(3)
    )
    _assert_branch_equals_jnp_copies(monkeypatch, q, k, v, sl, r, H, real_len=rl)


@pytest.mark.slow
def test_pack_direct_fully_oob_tail_block(rng, monkeypatch):
    """Regression: at the flagship-like fp32 r=16 geometry the VMEM budget
    drops the copy-kernel row block to bt=64, and m=129 pads to Mp=256 —
    so the direct unpack's naive grid would contain a block STARTING past
    L (2064 < 3*1024 < 4*1024 = Mp*r). Pallas clamps such a block
    backward (dynamic-slice semantics), overwriting the last valid rows
    with padded-row garbage; the grid must exclude it."""
    from gigapath_tpu.ops.pallas_dilated import _pack_bt

    H, Dh, r, L, sl = 16, 48, 16, 2064, 4096
    E = H * Dh
    assert _pack_bt(256, r, E, 4) == 64  # the geometry the test relies on
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, L, E)), jnp.float32) for _ in range(3)
    )
    _assert_branch_equals_jnp_copies(monkeypatch, q, k, v, sl, r, H, grads=False)


def test_pack_direct_fast_small_geometry(rng, monkeypatch):
    """Fast default-tier sibling of test_pack_direct_matches_padded:
    single-segment branch with a straddling tail block, forward
    bit-identity only (test_copy_kernels_are_exact covers gradients)."""
    L, sl, r, rl = 300, 512, 2, 277
    H, Dh = 8, 16
    E = H * Dh
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, L, E)), jnp.float32) for _ in range(3)
    )
    _assert_branch_equals_jnp_copies(
        monkeypatch, q, k, v, sl, r, H, grads=False, real_len=rl
    )


# (B, L, H, Dh, sl, r, dtype, windows, branch): one geometry of the copy
# kernels each; ``windows`` is what _copy_plan must decide from the shapes,
# ``branch`` what to run through the whole branch besides the copies alone:
# "" = nothing, "fwd" = forward with a static real_len 3 short of L, "grad" =
# gradients too, "traced" = those with a traced [B] valid_len on top
_COPY_CASES = {
    # r = 1, three segments on the block grid, the last one 40 of 128 rows
    "r1_segments_last_short": (1, 296, 4, 16, 128, 1, "float32", "grid", "grad"),
    # r = 2, g = 2^5 * 3: a window of bt*r = 256 rows reaches over the next
    # TWO segments' real tokens; they must pack as zeros, and unpack must
    # write none of their rows
    "r2_g96_reaches_next_segments": (2, 296, 8, 16, 96, 2, "float32", "element", ""),
    "r2_g96_traced_valid_len": (2, 296, 8, 16, 96, 2, "float32", "element", "traced"),
    # the flagship's r2 in small: g = 2^5 * 17, Mp * r = 768 > g, S = 3
    "r2_g544_mp_over_g": (1, 1184, 8, 16, 544, 2, "float32", "element", ""),
    "r2_g544_bf16_tile16": (1, 1184, 8, 16, 544, 2, "bfloat16", "element", ""),
    # one segment, bt*r = 1024: the window at 2048 straddles L = 2064, the
    # one at 3072 would start past it; float32 at r = 16 halves bt to 64
    "r16_f32_bt64_tail_past_L": (1, 2064, 16, 48, 4096, 16, "float32", "grid", "fwd"),
    "r4_one_segment_tail_straddles": (2, 523, 8, 16, 1024, 4, "float32", "grid", ""),
    "heads_of_64": (1, 608, 4, 64, 304, 2, "bfloat16", "element", ""),
    "heads_of_64_one_segment": (1, 304, 4, 64, 4096, 4, "float32", "grid", "fwd"),
    "heads_of_96": (1, 608, 4, 96, 304, 4, "bfloat16", "element", ""),
    "heads_of_96_segments": (1, 296, 4, 96, 96, 2, "float32", "element", "grad"),
    # a segment start off the sublane tile, or a sequence shorter than one
    # window: the geometries that keep the zero-padded view built by XLA
    "g60_off_the_tile_padded_view": (2, 300, 8, 16, 60, 2, "float32", "padded", "fwd"),
    "window_longer_than_L_padded_view": (2, 232, 8, 16, 96, 2, "float32", "padded", ""),
}


@pytest.mark.parametrize("case", sorted(_COPY_CASES))
def test_copy_kernels_are_exact(rng, monkeypatch, case):
    """The pack / unpack copy kernels on the dense [B, L, E] array against
    a plain jnp pack / unpack written here, bit for bit; and through the
    whole branch, forward and gradient."""
    import gigapath_tpu.ops.pallas_dilated as pdm

    B, L, H, Dh, sl, r, dtype, windows, branch = _COPY_CASES[case]
    E = H * Dh
    dtype = jnp.dtype(dtype)
    g, S, _, m, Mp, _ = pdm._branch_geometry(L, E, sl, r)
    decided, bt = pdm._copy_plan(L, g, S, r, Mp, E, dtype.itemsize)
    assert decided == windows
    if case.startswith("r16_f32"):
        assert bt == 64 and Mp * r > L + bt * r  # a window starts past L
    if "mp_over_g" in case:
        assert S > 1 and Mp * r > g
    x = jnp.asarray(rng.normal(size=(B, L, E)), dtype)
    packed = pdm._pack_phases(x, g, S, r, Mp, H, True)
    np.testing.assert_array_equal(
        np.asarray(packed, np.float32),
        np.asarray(_jnp_pack(x, g, S, r, Mp, H), np.float32))
    # every packed slot filled, padded ones too: unpack must drop those
    p6 = jnp.asarray(rng.normal(size=packed.shape), dtype)
    np.testing.assert_array_equal(
        np.asarray(pdm._unpack_phases(p6, L, E, g, S, r, True), np.float32),
        np.asarray(_jnp_unpack(p6, L, E, g, S, r), np.float32))
    if not branch:
        return
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, E)), dtype) for _ in range(3))
    kw = {"real_len": L - 3}
    if branch == "traced":
        kw["valid_len_dyn"] = jnp.asarray([L - 3, L - 70][:B], jnp.int32)
    _assert_branch_equals_jnp_copies(
        monkeypatch, q, k, v, sl, r, H, grads=branch != "fwd", **kw)


def _lowered_ops(text):
    """(operation line, scope path) of every operation in a lowered text
    made with ``debug_info=True``, once for each call of the function that
    holds it. The path of an operation in a jitted function is its call
    site's followed by its own (``.../pack/jit(_pack_call)/dilated_pack/...``),
    as the device trace names it: the operation's own path starts anew
    inside the function."""
    import re

    locs = dict(re.findall(r'^(#loc\d+) = loc\((.*)\)$', text, re.M))

    def path(ref, depth=0):
        body = locs.get(ref, "")
        found = re.match(r'"([^"]*)"', body)
        if found:
            return found.group(1)
        inner = re.search(r"#loc\d+", body)
        return path(inner.group(0), depth + 1) if inner and depth < 8 else ""

    ops, sites, func = [], {}, None
    for line in text.splitlines():
        defined = re.search(r"func\.func (?:public |private )?@([\w.]+)", line)
        if defined:
            func = defined.group(1)
        found = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if not (found and " = " in line):
            continue
        where = path(found.group(1))
        for callee in re.findall(r"\bcall @([\w.]+)\(", line):
            sites.setdefault(callee, []).append((func, where))
        ops.append((line, where, func))

    def prefixes(name, depth=0):
        """The call sites' paths of function ``name``, one a call."""
        if name not in sites or depth > 8:
            return [""]
        return [outer + where + "/" for caller, where in sites[name]
                for outer in prefixes(caller, depth + 1)]

    for line, where, func in ops:
        for outer in prefixes(func):
            yield line, outer + where


def _pack_glue(text, L, E):
    """Of a lowered text: the custom calls by kernel name, the number of
    operations under ``/pack/`` or ``/unpack/`` that are no kernel, and the
    (path, line) of each of those whose operand or result holds L*E
    elements or more. A call into a jitted function is no operation of its
    own: its body's operations stand under its path."""
    import re

    calls = {"dilated_pack": 0, "dilated_unpack": 0, "dilated_fwd_overlap": 0,
             "dilated_epilogue_fwd": 0}
    glue, dense = 0, []
    for line, path in _lowered_ops(text):
        if "tpu_custom_call" in line:
            name = re.search(r"(?:^|/)(dilated_\w+)/pallas_call", path)
            calls[name.group(1)] += 1
            continue
        if "/pack/" not in path + "/" and "/unpack/" not in path + "/":
            continue
        if re.search(r"\bcall @", line):
            continue
        glue += 1
        for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", line):
            if np.prod([int(d) for d in dims.split("x") if d]) >= L * E:
                dense.append((path, line[:200]))
    return calls, glue, dense


def _flagship_schedule_text(merge, L, B=1, H=16, Dh=48):
    """The flagship's five branches at a small L, lowered for the TPU
    (nothing compiles or runs): the op as it is called (``epilogue``), or
    its fallback where no plan exists (``dense``)."""
    import functools

    from gigapath_tpu.ops import dilated_attention as da
    from gigapath_tpu.ops import pallas_dilated as pdm

    segs, ratios = [128, 544, 2048, 4096, 8192], [1, 2, 4, 8, 16]
    if merge == "epilogue":
        op = jax.jit(functools.partial(
            da.dilated_attention_fused, segment_lengths=segs, dilated_ratios=ratios,
            valid_len=L - 127, flags=pdm.PipelineFlags()))
    else:
        op = jax.jit(functools.partial(
            da._fused_dense_merge, segment_lengths=segs, dilated_ratios=ratios,
            is_causal=False, real_len=L - 127, valid_dyn=None,
            streaming_fusion=False, interpret=False, flags=pdm.PipelineFlags()))
    x = jax.ShapeDtypeStruct((B, L, H, Dh), jnp.bfloat16)
    return op.trace(x, x, x).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)


@pytest.mark.parametrize("merge", ["epilogue", "dense"])
def test_flagship_schedule_lowers_without_dense_glue_around_the_copies(merge):
    """The flagship's five branches at a small L of the same structure
    (S > 1 for r1 and r2, S == 1 above), lowered for the TPU (nothing
    compiles or runs): under ``/pack/`` and ``/unpack/`` (the body of the
    jitted pack call included) stand the copy kernels' custom calls and
    operations on small arrays only; no operand or result there has L*E
    elements or more, so no dense-sized pad, slice or relayout is left
    around them. The op as it is called (``epilogue``: 9 pack calls a
    layer: for each of q, k and v one of r1, whose copies are the largest,
    one of r2, whose three segments' blocks would share steps, and one
    joint pass for r4 / r8 / r16; one merge epilogue over the packed
    results, no unpack) and its fallback where no plan exists (``dense``:
    15 + 5 copy calls, the lse scatter, an XLA merge)."""
    from gigapath_tpu.ops import pallas_dilated as pdm

    B, L, H, Dh = 1, 1184, 16, 48
    segs, ratios = [128, 544, 2048, 4096, 8192], [1, 2, 4, 8, 16]
    E = H * Dh
    for sl, r, want in zip(segs, ratios, ["grid", "element"] + ["grid"] * 3):
        g, S, _, _, Mp, _ = pdm._branch_geometry(L, E, sl, r)
        assert (S > 1) == (r <= 2)
        assert pdm._copy_plan(L, g, S, r, Mp, E, 2)[0] == want
    assert pdm.plan_stream_fusion(L, E, H, segs, ratios).straddles[1]
    calls, glue, dense = _pack_glue(_flagship_schedule_text(merge, L, B, H, Dh), L, E)
    assert dense == []
    copies = {"epilogue": (0, 1), "dense": (5, 0)}[merge]
    packs = {"epilogue": 9, "dense": 15}[merge]
    assert calls == {"dilated_pack": packs, "dilated_fwd_overlap": 5,
                     "dilated_unpack": copies[0], "dilated_epilogue_fwd": copies[1]}
    # the dense path's lse scatter is there, and small; the forward through the
    # epilogue holds nothing but the pack calls under these scopes
    assert (glue > 0) == (merge == "dense")


def test_the_glue_check_sees_a_dense_pad_inside_the_pack_call():
    """The check above reaches into the jitted pack call: at L = 1,176 the
    flagship's r2 takes the zero-padded view (``_copy_plan`` says
    ``"padded"``: XLA pads the dense array before the copy kernel), and
    that dense-sized pad, in the body of ``_pack_call``, is found under
    ``/pack/``."""
    from gigapath_tpu.ops import pallas_dilated as pdm

    B, L, H, Dh = 1, 1176, 16, 48
    E = H * Dh
    g, S, _, _, Mp, _ = pdm._branch_geometry(L, E, 544, 2)
    assert pdm._copy_plan(L, g, S, 2, Mp, E, 2)[0] == "padded"
    calls, glue, dense = _pack_glue(_flagship_schedule_text("epilogue", L, B, H, Dh), L, E)
    assert calls["dilated_pack"] == 9 and glue > 0
    assert dense and all("/pack/jit(_pack_call)/" in path for path, _ in dense)


# (B, L, H, Dh, segments, dtype, members, rows): the joint pass of a
# projection (``_pack_call``) for the five ratios 1-16; ``members`` and
# ``rows`` are what ``plan_pack`` must decide from the shapes. Every branch
# of the call's output, members and the branches with a call of their own
# alike, is held to the plain jnp pack
_JOINT_CASES = {
    # the flagship's structure at the small L: r1 (the largest copies) and
    # r2 (S = 3, its segments' blocks would share steps) keep their own
    # calls; r4-r16 share 512-row windows, the fourth starting past L
    "flagship_1184_bf16": (2, 1184, 16, 48, (128, 544, 2048, 4096, 8192), "bfloat16",
                           (2, 3, 4), 512),
    "flagship_1184_f32": (1, 1184, 16, 48, (128, 544, 2048, 4096, 8192), "float32",
                          (2, 3, 4), 256),
    # r2 joins: its second segment starts 160 rows past the window grid,
    # so each of its blocks there is joined from two windows, as at the
    # flagship's 10,368 tokens (5,792 = 11 x 512 + 160)
    "r2_two_segments_joined": (2, 288, 16, 16, (128, 160, 4096, 4096, 8192), "bfloat16",
                               (1, 2, 3, 4), 256),
    # three segments, offsets 0, 160 and 64 rows past the grid
    "r2_three_segments_joined": (1, 352, 16, 16, (128, 160, 4096, 4096, 8192), "bfloat16",
                                 (1, 2, 3, 4), 256),
    "r2_three_segments_joined_f32": (1, 352, 16, 16, (128, 160, 4096, 4096, 8192),
                                     "float32", (1, 2, 3, 4), 256),
}


def _plan(pdm, L, E, H, segs, itemsize, interpret=True, ratios=(1, 2, 4, 8, 16)):
    geoms = tuple((g, S, r, Mp) for (g, S, _, _, Mp, _), r in zip(
        (pdm._branch_geometry(L, E, sl, r) for sl, r in zip(segs, ratios)), ratios))
    return pdm.plan_pack(L, E, H, geoms, itemsize, interpret)


@pytest.mark.parametrize("case", sorted(_JOINT_CASES))
def test_joint_pack_is_exact(rng, case):
    """One read of the dense [B, L, E] projection writes every member's
    packed copy, bit for bit the plain jnp pack's, padded rows exact zeros."""
    import gigapath_tpu.ops.pallas_dilated as pdm

    B, L, H, Dh, segs, dtype, members, rows = _JOINT_CASES[case]
    E = H * Dh
    dtype = jnp.dtype(dtype)
    plan = _plan(pdm, L, E, H, segs, dtype.itemsize)
    assert (plan.members, plan.rows) == (members, rows)
    x = jnp.asarray(rng.normal(size=(B, L, E)), dtype)
    packed = pdm._pack_call(x, plan=plan)
    for (g, S, r, Mp), got in zip(plan.geoms, packed):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32),
            np.asarray(_jnp_pack(x, g, S, r, Mp, H), np.float32))


def _kernel_runs(jaxpr, name):
    """Runs of the ``pallas_call`` named ``name`` in a jaxpr, through the
    jitted functions and custom VJPs it calls."""
    from jax.extend.core import Jaxpr

    runs = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            runs += eqn.params["name"] == name
        for param in eqn.params.values():
            for sub in param if isinstance(param, (tuple, list)) else (param,):
                sub = getattr(sub, "jaxpr", sub)
                if isinstance(sub, Jaxpr):
                    runs += _kernel_runs(sub, name)
    return runs


def test_fused_op_with_the_joint_pass_equals_the_per_branch_packs(rng, monkeypatch):
    """The merge-epilogue op, forward and gradients, with the joint pass
    (r2 and r4 in one pass, r2's second segment joined from two windows,
    r1 its own call) against the same op with every branch packed by a
    call of its own: bit for bit, a traced valid length on top."""
    import gigapath_tpu.ops.pallas_dilated as pdm

    B, L, H, Dh, segs, ratios = 2, 176, 4, 8, (32, 160, 4096), (1, 2, 4)
    E = H * Dh
    dtype = jnp.dtype(jnp.bfloat16)  # three branches' gradients summed in it
    packing = _plan(pdm, L, E, H, segs, dtype.itemsize, ratios=ratios)
    assert (packing.rows, packing.members) == (128, (1, 2))
    plan = pdm.plan_stream_fusion(L, E, H, segs, ratios, interpret=True,
                                  itemsize=dtype.itemsize)
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, E)), dtype) for _ in range(3))
    valid = jnp.asarray([L - 5, L - 70], jnp.int32)

    def op(q_, k_, v_):
        return pdm.dilated_attention_stream_fused(
            q_, k_, v_, segs, ratios, H, plan, real_len=L - 3,
            valid_len_dyn=valid, flags=pdm.PipelineFlags())

    def both(q_, k_, v_):  # the output, and the gradients it pulls back
        out, pull = jax.vjp(op, q_, k_, v_)
        return (out,) + pull(out)

    def run():
        calls = _kernel_runs(jax.make_jaxpr(op)(q, k, v).jaxpr, "dilated_pack")
        return calls, jax.jit(both)(q, k, v)

    joint_calls, got = run()
    # traces of the op are cached by its static arguments: drop them on
    # both sides of the patch
    monkeypatch.setattr(pdm, "_joins", lambda *a: False)
    jax.clear_caches()
    try:
        own_calls, want = run()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    # q, k and v: one call a branch, or the joint pass and a call a non-member
    assert (joint_calls, own_calls) == (3 * (1 + len(ratios) - len(packing.members)),
                                        3 * len(ratios))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
