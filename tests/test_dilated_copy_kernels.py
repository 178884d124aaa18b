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
    made with ``debug_info=True``."""
    import re

    locs = dict(re.findall(r'^(#loc\d+) = loc\((.*)\)$', text, re.M))

    def path(ref, depth=0):
        body = locs.get(ref, "")
        found = re.match(r'"([^"]*)"', body)
        if found:
            return found.group(1)
        inner = re.search(r"#loc\d+", body)
        return path(inner.group(0), depth + 1) if inner and depth < 8 else ""

    for line in text.splitlines():
        found = re.search(r"loc\((#loc\d+)\)\s*$", line)
        if found and " = " in line:
            yield line, path(found.group(1))


@pytest.mark.parametrize("merge", ["epilogue", "dense"])
def test_flagship_schedule_lowers_without_dense_glue_around_the_copies(merge):
    """The flagship's five branches at a small L of the same structure
    (S > 1 for r1 and r2, S == 1 above), lowered for the TPU (nothing
    compiles or runs): under ``/pack/`` and ``/unpack/`` stand the copy
    kernels' custom calls and operations on small arrays only; no operand
    or result there has L*E elements or more, so no dense-sized pad, slice
    or relayout is left around them. The op as it is called (``epilogue``:
    15 pack calls a layer, one merge epilogue over the packed results, no
    unpack) and its fallback where no plan exists (``dense``: 15 + 5 copy
    calls, the lse scatter, an XLA merge)."""
    import functools
    import re

    from gigapath_tpu.ops import dilated_attention as da
    from gigapath_tpu.ops import pallas_dilated as pdm

    B, L, H, Dh = 1, 1184, 16, 48
    segs, ratios = [128, 544, 2048, 4096, 8192], [1, 2, 4, 8, 16]
    E = H * Dh
    for sl, r, want in zip(segs, ratios, ["grid", "element"] + ["grid"] * 3):
        g, S, _, _, Mp, _ = pdm._branch_geometry(L, E, sl, r)
        assert (S > 1) == (r <= 2)
        assert pdm._copy_plan(L, g, S, r, Mp, E, 2)[0] == want
    assert pdm.plan_stream_fusion(L, E, H, segs, ratios).straddles[1]
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
    text = op.trace(x, x, x).lower(lowering_platforms=("tpu",)).as_text(debug_info=True)
    calls = {"dilated_pack": 0, "dilated_unpack": 0, "dilated_fwd_overlap": 0,
             "dilated_epilogue_fwd": 0}
    glue = 0
    for line, path in _lowered_ops(text):
        if "tpu_custom_call" in line:
            name = re.search(r"(?:^|/)(dilated_\w+)/pallas_call", path)
            calls[name.group(1)] += 1
            continue
        if "/pack/" not in path + "/" and "/unpack/" not in path + "/":
            continue
        glue += 1
        for dims in re.findall(r"tensor<((?:\d+x)+)[a-z]", line):
            size = np.prod([int(d) for d in dims.split("x") if d])
            assert size < L * E, (path, line[:200])
    copies = {"epilogue": (0, 1), "dense": (5, 0)}[merge]
    assert calls == {"dilated_pack": 15, "dilated_fwd_overlap": 5,
                     "dilated_unpack": copies[0], "dilated_epilogue_fwd": copies[1]}
    # the dense path's lse scatter is there, and small; the forward through the
    # epilogue holds nothing but the pack calls under these scopes
    assert (glue > 0) == (merge == "dense")
