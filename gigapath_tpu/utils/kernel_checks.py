"""Compiled-kernel correctness checks against the float32 reference.

The one copy of the on-chip kernel gate, shared by ``chip_smoke.py``
(phase A) and ``scripts/tpu_selfcheck.py``: every Pallas attention path the
flagship slide encoder dispatches to — flash, head-major (bhld), phase-major
(fused), both backward families, every block triple the adaptive dispatcher
picks at the bench geometry, the streaming ``pair_partial`` fold — and the
ViT tile encoder's attention core over packed qkv, compared
with the jnp tier on float32 inputs under
``jax.default_matmul_precision("highest")``; and the dilated branches' pack /
unpack copy kernels against a plain jnp pack / unpack, exactly.

Callers decide what a failure costs; this module only measures. It never
asks which backend it is on: the caller runs it on a TPU (or, for the CPU
rehearsal, under ``pltpu.force_tpu_interpret_mode()`` at ``TINY``).
"""

from __future__ import annotations

import os
from typing import Callable, Dict, List, NamedTuple, Sequence

import jax
import jax.numpy as jnp
import numpy as np


class Geometry(NamedTuple):
    """Shapes one run of the checks uses."""

    heads: int
    head_dim: int
    segment_lengths: Sequence[int]
    dilated_ratios: Sequence[int]
    seq_len: int          # flash / bhld / fused forward checks
    grad_segments: Sequence[int]   # short schedule of the backward checks
    grad_ratios: Sequence[int]
    grad_len: int
    bench_len: int        # block-coverage length (bench N + cls token)
    serve_len: int        # a serve bucket + cls token: smaller blocks, one key block
    fold_chunk: int       # streaming pair_partial chunk
    vit_attn: Sequence[int]        # (B, N, heads, head_dim) of the ViT's packed-qkv core


def flagship(bench_tokens: int = 10240) -> Geometry:
    from gigapath_tpu.models.longnet_config import flagship_geometry

    g = flagship_geometry()
    return Geometry(
        heads=g["heads"], head_dim=g["head_dim"],
        segment_lengths=g["segment_lengths"],
        dilated_ratios=g["dilated_ratios"],
        # L=2048 keeps the dense [L, L] reference logits small while still
        # exercising multi-segment branch 1 and every dilation ratio
        seq_len=2048,
        grad_segments=[256, 512], grad_ratios=[1, 2], grad_len=1024,
        bench_len=bench_tokens + 1, serve_len=4096 + 1, fold_chunk=2048,
        vit_attn=(2, 197, 24, 64),  # ViT-G/14
    )


# the CPU rehearsal's size: interpret-mode Pallas, seconds not minutes
TINY = Geometry(
    heads=4, head_dim=8, segment_lengths=[32, 64], dilated_ratios=[1, 2],
    seq_len=128, grad_segments=[32, 64], grad_ratios=[1, 2], grad_len=64,
    bench_len=65, serve_len=33, fold_chunk=32, vit_attn=(2, 19, 2, 64),
)


def _max_err(got, ref) -> float:
    return float(jnp.abs(
        jnp.asarray(got, jnp.float32) - jnp.asarray(ref, jnp.float32)
    ).max())


def run_kernel_checks(
    geom: Geometry,
    *,
    seed: int = 0,
    flagged_variants: bool = False,
    report: Callable[[Dict], None] = lambda row: None,
) -> List[Dict]:
    """Run every check at ``geom``; returns one row per comparison:
    ``{"name", "max_abs_err", "atol", "ok"}`` (``report`` sees each row as
    it lands). A NaN error is not ``ok``."""
    from gigapath_tpu.ops import dilated_attention as da
    from gigapath_tpu.ops import pallas_flash as pf
    from gigapath_tpu.ops.attention import attention_with_lse
    from gigapath_tpu.ops.pallas_streaming import pallas_pair_partial
    from gigapath_tpu.ops.streaming_prefill import pair_partial_attention

    rows: List[Dict] = []

    def check(name, got, ref, atol):
        err = _max_err(got, ref)
        row = {"name": name, "max_abs_err": err, "atol": atol,
               "ok": bool(err <= atol)}  # NaN <= atol is False
        rows.append(row)
        report(row)

    def rel_check(name, got, ref, atol, cut=None):
        """Error relative to the reference's max magnitude."""
        got = jnp.asarray(got, jnp.float32)
        ref = jnp.asarray(ref, jnp.float32)
        if cut is not None:
            got, ref = got[:, :, :cut], ref[:, :, :cut]
        scale = max(float(jnp.abs(ref).max()), 1e-12)
        check(name, got / scale, ref / scale, atol)

    highest = jax.default_matmul_precision("highest")
    rng = np.random.default_rng(seed)
    H, Dh = geom.heads, geom.head_dim
    SEGS, RATIOS = list(geom.segment_lengths), list(geom.dilated_ratios)
    L = geom.seq_len
    vl = L - 47  # ragged tail

    def qkv(*shape):
        return tuple(
            jnp.asarray(rng.normal(size=shape), jnp.bfloat16) for _ in range(3)
        )

    q, k, v = qkv(1, L, H, Dh)
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))

    # plain flash kernel (bf16 inputs; fp32 softmax both sides)
    o_p, l_p = pf.pallas_flash_attention(q, k, v)
    with highest:
        o_j, l_j = attention_with_lse(qf, kf, vf)
    check(f"pallas flash fwd (L={L})", o_p, o_j, 3e-2)
    check(f"pallas flash lse (L={L})", l_p, l_j, 3e-2)

    # head-major and phase-major dilated paths vs the jnp tier
    with highest:
        ref = da.dilated_attention_bhld(
            qf, kf, vf, SEGS, RATIOS, valid_len=vl, use_pallas=False
        )
    out = da.dilated_attention_bhld(
        q, k, v, SEGS, RATIOS, valid_len=vl, use_pallas=True
    )
    check("dilated bhld fwd (valid_len)", out[:, :vl], ref[:, :vl], 5e-2)
    out_f = da.dilated_attention_fused(q, k, v, SEGS, RATIOS, valid_len=vl)
    check("dilated fused fwd (valid_len)", out_f[:, :vl], ref[:, :vl], 5e-2)

    # gradients through both backward families; dq/dk/dv ride ONE
    # jax.grad(argnums=(0,1,2)) per path (one compile covers all three)
    segs, ratios, Lb = list(geom.grad_segments), list(geom.grad_ratios), geom.grad_len
    qb, kb, vb = q[:, :Lb], k[:, :Lb], v[:, :Lb]

    def loss_bhld(x, y, z, use_pallas):
        return da.dilated_attention_bhld(
            x, y, z, segs, ratios, use_pallas=use_pallas
        ).astype(jnp.float32).var()

    def loss_fused_short(x, y, z):
        return da.dilated_attention_fused(
            x, y, z, segs, ratios
        ).astype(jnp.float32).var()

    def grad3(f, **jit_kwargs):
        return jax.jit(jax.grad(f, argnums=(0, 1, 2)), **jit_kwargs)

    g_p = grad3(loss_bhld, static_argnums=3)(qb, kb, vb, True)
    g_f = grad3(loss_fused_short)(qb, kb, vb)
    with highest:
        g_j = grad3(loss_bhld, static_argnums=3)(
            qf[:, :Lb], kf[:, :Lb], vf[:, :Lb], False
        )
    for name, a, b, c in zip("qkv", g_p, g_f, g_j):
        rel_check(f"dilated bhld d{name}", a, c, 6e-2)
        rel_check(f"dilated fused d{name}", b, c, 6e-2)

    # --- bench-geometry block coverage (fwd AND bwd) --------------------
    # every distinct (fwd block, bwd block pair, flat?) the adaptive
    # dispatcher can choose at the bench length must compile and run in
    # both directions — the shape class that once shipped a backward
    # scoped-VMEM overflow (the 1408 single-block branch) to the driver
    N = geom.bench_len
    seen = {}
    for sl, r in zip(SEGS, RATIOS):
        g, _Lp, _n, _gp, _m, block = da._bhld_geom(N, sl, r)
        bq, bk = pf.bwd_blocks(block)
        # flat (zero-glue) and segmented paths are DIFFERENT kernels even
        # at the same block triple
        seen.setdefault((block, bq, bk, da._flat_eligible(g, r)), (sl, r))
    qN, kN, vN = qkv(1, H, N, Dh)
    qNf, kNf, vNf = (x.astype(jnp.float32) for x in (qN, kN, vN))
    for (block, bq, bk, flat), (sl, r) in sorted(seen.items()):
        tag = f"sl={sl} r={r} blk={block} bwd=({bq},{bk})" + (" flat" if flat else "")
        # a near-empty tail segment (the r=1 branch's 1-token tail at
        # 10241 = 10x1024 + 1) has analytically-zero dq/dk — softmax over
        # one key — so both paths hold only rounding noise there
        tail = N % min(sl, N)
        cmp_len = N - tail if 0 < tail < 8 else N

        def branch_loss(x, y, z, use_pallas):
            o, _ = da._branch_bhld(
                x, y, z, sl, r, is_causal=False, real_len=N,
                interpret=False, use_pallas=use_pallas,
            )
            return (o.astype(jnp.float32) ** 2).mean()

        vg = jax.jit(
            jax.value_and_grad(branch_loss, argnums=(0, 1, 2)), static_argnums=3
        )
        loss_p, grads_p = vg(qN, kN, vN, True)
        with highest:
            loss_j, grads_j = vg(qNf, kNf, vNf, False)
        check(f"bench-geom fwd {tag}", loss_p, loss_j, 1e-3)
        for name, a, b in zip("qkv", grads_p, grads_j):
            cut = N if name == "v" else cmp_len  # dv exact on 1-key segs
            rel_check(f"bench-geom d{name} {tag}", a, b, 6e-2, cut=cut)

    # --- fused (phase-major, the DEFAULT) path at the bench geometry ----
    # including the traced-valid-len variant the fine-tune train path uses
    def fused_loss(x, y, z, n_valid):
        o = da.dilated_attention_fused(x, y, z, SEGS, RATIOS, valid_len=n_valid)
        return (o.astype(jnp.float32) ** 2).mean()

    # static_argnums: a jitted int operand would be traced, silently
    # routing the "static" check through the dynamic-kvlen path too
    vg_static = jax.jit(
        jax.value_and_grad(fused_loss, argnums=(0, 1, 2)), static_argnums=3
    )
    vg_traced = jax.jit(jax.value_and_grad(fused_loss, argnums=(0, 1, 2)))
    # the bench length, and a serve bucket (other blocks, r8 / r16 one key
    # block a row): forward and backward against the float32 reference
    fused = {}
    for tag, n in (("bench-geom", N), ("serve-bucket", geom.serve_len)):
        n_valid = n - min(64, n // 4)

        def bhld_ref_loss(x, y, z):
            o = da.dilated_attention_bhld(
                x, y, z, SEGS, RATIOS, valid_len=n_valid, use_pallas=False
            )
            return (o.astype(jnp.float32) ** 2).mean()

        qs, ks, vs = qkv(1, n, H, Dh)
        loss_s, grads_s = vg_static(qs, ks, vs, n_valid)
        loss_t, grads_t = vg_traced(qs, ks, vs, jnp.asarray([n_valid], jnp.int32))
        with highest:
            loss_b, grads_b = jax.jit(
                jax.value_and_grad(bhld_ref_loss, argnums=(0, 1, 2))
            )(*(x.astype(jnp.float32) for x in (qs, ks, vs)))
        check(f"fused {tag} fwd (static vl)", loss_s, loss_b, 1e-3)
        check(f"fused {tag} fwd (traced vl == static)", loss_t, loss_s, 1e-6)
        for name, a, t, b in zip("qkv", grads_s, grads_t, grads_b):
            rel_check(f"fused {tag} d{name}", a, b, 6e-2)
            check(f"fused {tag} d{name} traced==static", t, a, 1e-6)
        fused[tag] = ((qs, ks, vs), loss_s, grads_s)

    # --- streaming fold: pallas pair_partial vs the jnp fold ------------
    C = geom.fold_chunk
    qc, kc, vc = qkv(1, C, H, Dh)
    for sl, r in zip(SEGS, RATIOS):
        # segments longer than a chunk: the second chunk's queries against
        # the first chunk's keys; shorter ones: the diagonal pair (any
        # other is fully masked). Ragged tail inside the key chunk either
        # way, so segment, phase and valid masks are all live.
        args = (jnp.int32(C if sl > C else 0), jnp.int32(0))
        kw = dict(segment_len=int(sl), ratio=int(r), valid_len=jnp.int32(C - 17))
        o_p, l_p = pallas_pair_partial(qc, kc, vc, *args, **kw)
        with highest:
            o_j, l_j = pair_partial_attention(
                *(x.astype(jnp.float32) for x in (qc, kc, vc)), *args, **kw
            )
        covered = np.asarray(l_j) > -1e8 * 0.5  # NEG_INF sentinel rows
        check(f"pair_partial out sl={sl} r={r}", o_p, o_j, 3e-2)
        check(
            f"pair_partial lse sl={sl} r={r}",
            np.where(covered, np.asarray(l_p), 0.0),
            np.where(covered, np.asarray(l_j), 0.0), 3e-2,
        )

    # --- the ViT tile encoder's attention core over packed qkv ----------
    from gigapath_tpu.ops import pallas_vit_attention as pva

    Bv, Nv, Hv, Dv = geom.vit_attn
    packed = jnp.asarray(rng.normal(size=(Bv, Nv, 3 * Hv * Dv)), jnp.bfloat16)
    with highest:
        ref = pva.packed_qkv_attention_jnp(packed.astype(jnp.float32), Hv)
    check(f"vit packed-qkv attention fwd (N={Nv}, {Hv}x{Dv})",
          pva.packed_qkv_attention(packed, Hv), ref, 3e-2)

    _copy_kernel_checks(geom, rng, check)
    _forward_body_checks(geom, rng, check)

    if flagged_variants:
        _flagged_variant_checks(
            da, SEGS, RATIOS, N, *fused["bench-geom"], check, rel_check
        )
    return rows


def _jnp_pack(x, g, S, r, Mp, H):
    """Plain jnp [B, L, E] -> [B, S, r, hb, Mp, Dh]: packed row j of
    (segment s, phase p) is token s*g + j*r + p, heads p*hb .. (p+1)*hb - 1,
    zeros past the segment's or the sequence's end."""
    B, L, E = x.shape
    hb, Dh = H // r, E // H
    x = jnp.pad(x, ((0, 0), (0, S * g - L), (0, 0))).reshape(B, S, g, E)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, Mp * r - g), (0, 0)))
    x = x.reshape(B, S, Mp, r, r, hb, Dh)  # [.., row, phase, band, head, :]
    diag = jnp.stack([x[:, :, :, p, p] for p in range(r)], axis=2)
    return diag.transpose(0, 1, 2, 4, 3, 5)


def _jnp_unpack(p6, L, E, g, S, r):
    """Plain jnp inverse of :func:`_jnp_pack`; off-band lanes are zeros."""
    B, _, _, hb, Mp, Dh = p6.shape
    x = jnp.zeros((B, S, Mp, r, r, hb, Dh), p6.dtype)
    for p in range(r):
        x = x.at[:, :, :, p, p].set(p6[:, :, p].transpose(0, 1, 3, 2, 4))
    x = x.reshape(B, S, Mp * r, E)[:, :, :g]
    return x.reshape(B, S * g, E)[:, :L]


def _copy_kernel_checks(geom: Geometry, rng, check) -> None:
    """The pack / unpack copy kernels, compiled, against a plain jnp pack /
    unpack, exact equality: the schedule's every branch at the
    bench length as the slide encoder pads it (bfloat16), and the wider
    encoders' heads of 64 and 96 at the two branches whose windows differ
    most (r = 2: element-offset windows; the last: the largest r * E)."""
    from gigapath_tpu.ops import pallas_dilated as pd

    H = geom.heads
    L = -(-geom.bench_len // 128) * 128
    branches = list(zip(geom.segment_lengths, geom.dilated_ratios))
    cases = [(geom.head_dim, sl, r) for sl, r in branches]
    cases += [(Dh, *branches[i]) for Dh in (64, 96) for i in (1, -1)]
    cases = list(dict.fromkeys(cases))  # a two-branch schedule names one twice
    for Dh, sl, r in cases:
        E = H * Dh
        g, S, _, _, Mp, _ = pd._branch_geometry(L, E, sl, r)
        x = jnp.asarray(rng.normal(size=(2, L, E)), jnp.bfloat16)
        p6 = jnp.asarray(rng.normal(size=(2, S, r, H // r, Mp, Dh)), jnp.bfloat16)
        pack = jax.jit(lambda a: pd._pack_phases(a, g, S, r, Mp, H, False))
        unpack = jax.jit(lambda a: pd._unpack_phases(a, L, E, g, S, r, False))
        tag = f"sl={sl} r={r} Dh={Dh} L={L}"
        # the number compared is the count of elements that differ: one fails
        check(f"copy pack {tag}: elements that differ",
              jnp.sum(pack(x) != _jnp_pack(x, g, S, r, Mp, H)), 0, 0.5)
        check(f"copy unpack {tag}: elements that differ",
              jnp.sum(unpack(p6) != _jnp_unpack(p6, L, E, g, S, r)), 0, 0.5)
    # the pack of a projection for the whole schedule: the joint pass and
    # the branches' own calls, as the fused op calls them
    for Dh in dict.fromkeys((geom.head_dim, 64, 96)):
        E = H * Dh
        geoms = tuple(
            (g, S, r, Mp) for (g, S, _, _, Mp, _), (_, r) in zip(
                (pd._branch_geometry(L, E, sl, r) for sl, r in branches), branches))
        plan = pd.plan_pack(L, E, H, geoms, 2)
        x = jnp.asarray(rng.normal(size=(2, L, E)), jnp.bfloat16)
        packed = jax.jit(lambda a: pd._pack_call(a, plan=plan))(x)
        differ = sum(jnp.sum(p != _jnp_pack(x, g, S, r, Mp, H))
                     for p, (g, S, r, Mp) in zip(packed, geoms))
        check(f"joint pack Dh={Dh} L={L} rows={plan.rows} members={plan.members}: "
              "elements that differ", differ, 0, 0.5)


def _forward_body_checks(geom: Geometry, rng, check) -> None:
    """The forward body the planner names for each branch of the schedule
    (``pallas_dilated.plan_fwd_body``: the overlapped one, non-causal)
    against the serial body on the same packed arrays, at the bench length
    and at a serve bucket, ragged key counts: the same arithmetic, so the
    number compared is the count of elements that differ."""
    from gigapath_tpu.ops import pallas_dilated as pd

    H, Dh = geom.heads, geom.head_dim
    for n in (geom.bench_len, geom.serve_len):
        L = -(-n // 128) * 128
        for sl, r in zip(geom.segment_lengths, geom.dilated_ratios):
            g, S, _, m, Mp, block = pd._branch_geometry(L, H * Dh, sl, r)
            hb = H // r
            q6, k6, v6 = (
                jnp.asarray(rng.normal(size=(2, S, r, hb, Mp, Dh)), jnp.bfloat16)
                for _ in range(3)
            )
            kvlen = jnp.asarray(np.stack([
                pd._phase_kvlen(S, g, r, m, n), pd._phase_kvlen(S, g, r, m, n // 3),
            ]))
            fwd = lambda body: jax.jit(lambda a, b, c, kl: pd._packed_forward(
                a, b, c, kl, False, hb, Dh, block, False, body))(q6, k6, v6, kvlen)
            (o_new, l_new), (o_old, l_old) = fwd(None), fwd("serial")
            plan = pd.plan_fwd_body(False, hb, block)
            tag = f"sl={sl} r={r} n={n} blk={block} {plan.body}x{plan.heads}"
            check(f"fwd body out {tag}: elements that differ",
                  jnp.sum(o_new != o_old), 0, 0.5)
            check(f"fwd body lse {tag}: elements that differ",
                  jnp.sum(l_new[..., :hb] != l_old[..., :hb]), 0, 0.5)


def _flagged_variant_checks(da, SEGS, RATIOS, N, qkv_b, loss_f, grads_f,
                            check, rel_check) -> None:
    """The default-off env-flagged kernel variants at the bench geometry:
    each must compile and agree with the default fused path on chip before
    any dispatch default flips to it. Flags are read at trace time; a fresh
    function identity per combo defeats the jit cache."""
    qb, kb, vb = qkv_b

    def make_fused_loss():
        def f(x, y, z, n_valid):
            o = da.dilated_attention_fused(x, y, z, SEGS, RATIOS, valid_len=n_valid)
            return (o.astype(jnp.float32) ** 2).mean()

        return f

    combos = [
        ("pipebwd", {"GIGAPATH_PIPELINED_BWD": "1"}, 1e-6),  # fwd unchanged
    ]
    for tag, env, tol in combos:
        prior = {key: os.environ.get(key) for key in env}
        os.environ.update(env)
        try:
            vg = jax.jit(
                jax.value_and_grad(make_fused_loss(), argnums=(0, 1, 2)),
                static_argnums=3,
            )
            loss_v, grads_v = vg(qb, kb, vb, N - 64)
            # traced valid_len (the fine-tune train path) on the same combo
            loss_tv, _ = jax.jit(
                jax.value_and_grad(make_fused_loss(), argnums=(0, 1, 2))
            )(qb, kb, vb, jnp.asarray([N - 64], jnp.int32))
        finally:
            for key, val in prior.items():
                if val is None:
                    os.environ.pop(key, None)
                else:
                    os.environ[key] = val
        check(f"flagged[{tag}] bench-geom fwd", loss_v, loss_f, tol)
        check(f"flagged[{tag}] traced vl == static", loss_tv, loss_v, 1e-6)
        for name, a, b in zip("qkv", grads_v, grads_f):
            rel_check(f"flagged[{tag}] d{name}", a, b, 1e-2)
