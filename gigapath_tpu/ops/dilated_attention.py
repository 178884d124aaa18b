"""Dilated attention (LongNet) — the long-context core of the slide encoder.

TPU-native counterpart of reference
``torchscale/component/dilated_attention.py``. Behavior parity:

- For each branch ``(segment_length sl, dilation r)`` the sequence is chopped
  into segments of ``min(sl, L)``; within a segment, heads are partitioned
  into ``r`` phase groups and head group ``p`` attends only positions
  ``p, p+r, ...`` (the reference implements this as a head-rotating
  einops-diagonal trick, ``dense_to_sparse:16-31``; here dilation is static
  phase *slices* — every index is a trace-time constant, so XLA lowers it
  to strided copies; TPU gathers/scatters over the token axis are slow).
- Attention runs per sparse segment through an op returning ``(out, lse)``.
- Three execution tiers, dispatched automatically: a head-major (BHLD)
  Pallas fast path on TPU (one relayout per op, segment-grid flash
  kernels), the phase-major fused kernels of
  :mod:`gigapath_tpu.ops.pallas_dilated` (opt-in), and a generic jnp path
  (CPU, dropout, traced masks, cross-attention, sequence parallelism).
- Branch outputs are scattered back to dense positions (uncovered positions
  get ``lse = NEG_INF``) and fused by softmax-weighting of the LSEs across
  branches (``scattering:100-131``); like the reference, the fusion weights
  are treated as constants in the backward pass (stop_gradient vs the
  reference's ``torch.no_grad``).
- Sequence parallelism: when a branch's segment spans more than the local
  sequence shard, K/V are all-gathered along the mesh ``seq`` axis and sliced
  to the ranks forming the current segment (``gather_kv:55-74``), queries
  staying local. The reference ships this dormant (never enabled); here it is
  a first-class code path driven by ``seq_axis_name`` inside ``shard_map``
  and covered by multi-device tests. Under ``GIGAPATH_RING_ATTN``
  (``PipelineFlags.ring_attn``) the oversized branches instead RING: local
  sparse K/V chunks rotate around the segment's sub-ring via ``ppermute``,
  partial attention runs per resident chunk, and partials merge through the
  stored-LSE online softmax — per-shard memory O(local chunk) instead of
  O(full segment), collectives overlapped with compute, with a custom VJP
  that rings in reverse (see the ring section below).

Everything is static-shape: the branch loop is a Python loop over a static
tuple, so ``jit`` unrolls it (5 branches in the flagship configs).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

import numpy as np

from gigapath_tpu.ops.attention import NEG_INF, MultiheadAttention, attention_with_lse
from gigapath_tpu.ops.common import round_up as _round_up

AttnFn = Callable[..., Tuple[jnp.ndarray, jnp.ndarray]]


from gigapath_tpu.ops.common import env_flag as _env_flag  # shared convention


_WARNED: set = set()


def _warn_once(msg: str) -> None:
    """One warning per distinct message per process (dispatch runs inside
    trace-time Python, so an unguarded warn would fire on every retrace)."""
    if msg not in _WARNED:
        _WARNED.add(msg)
        import warnings

        warnings.warn(msg, stacklevel=3)


def _kv_valid_lengths(
    batch: int, n_seg: int, seg_len: int, ratio: int, m: int, num_heads: int, real_len: int
) -> Optional[np.ndarray]:
    """Static per-(batch*segment, head) count of sparse key slots that fall
    inside the real sequence (zero-padding from segmenting/dilation is
    excluded).

    The reference lets zero-pad keys participate in the softmax
    (``dense_to_sparse`` pads with zeros and flash attention sees them as
    logit-0 keys); masking them instead is strictly better math at segment
    tails. Returns ``[batch*n_seg, H]`` int or None when everything is
    valid. All inputs are trace-time constants, so this is free under jit.
    """
    heads_per_group = -(-num_heads // ratio)
    phases = np.arange(num_heads) // heads_per_group  # [H]
    seg = np.arange(n_seg)[:, None]
    # valid j satisfy seg*g + phase + ratio*j < real_len
    counts = np.ceil((real_len - seg * seg_len - phases[None, :]) / ratio)
    counts = np.clip(counts, 0, m).astype(np.int32)  # [n_seg, H]
    if (counts == m).all():
        return None
    return np.tile(counts, (batch, 1))  # [batch*n_seg, H]


def _pad_to_multiple(x: jnp.ndarray, mult: int, axis: int) -> jnp.ndarray:
    rem = x.shape[axis] % mult
    if rem == 0:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, mult - rem)
    return jnp.pad(x, pads)


def _phase_head_ranges(num_heads: int, ratio: int):
    """Static (phase, head_start, head_end) triples: heads [hs, he) share
    ``phase`` — phases are contiguous head ranges by construction
    (``arange(H) // ceil(H/r)``), which is what makes the slice formulations
    below pure static slices."""
    heads_per_group = -(-num_heads // ratio)
    ranges = []
    for p in range(ratio):
        hs = p * heads_per_group
        he = min((p + 1) * heads_per_group, num_heads)
        if hs >= num_heads:
            break
        ranges.append((p, hs, he))
    return ranges


def dense_to_sparse(x: jnp.ndarray, ratio: int) -> jnp.ndarray:
    """Dilated subsample of segments: [b, g, H, D] -> [b, m, H, D], m=ceil(g/r).

    Head ``h`` keeps positions ``phase(h) + r*j``. Implemented as static
    phase slices of the ``[b, m, r, H, D]`` view concatenated over the head
    axis — every index is a trace-time constant, so XLA lowers this to plain
    strided copies (measured ~8x cheaper than the one-hot einsum select,
    whose ``r``-contraction forces a relayout; gathers over the token axis
    are slower still).
    """
    if ratio == 1:
        return x
    b, g, H, Dh = x.shape
    x = _pad_to_multiple(x, ratio, axis=1)
    m = x.shape[1] // ratio
    x5 = x.reshape(b, m, ratio, H, Dh)
    parts = [x5[:, :, p, hs:he, :] for p, hs, he in _phase_head_ranges(H, ratio)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=2)


def sparse_to_dense(
    out_s: jnp.ndarray, lse_s: jnp.ndarray, ratio: int, seg_len: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Scatter sparse branch results back to dense segment positions.

    ``out_s`` [b, m, H, D], ``lse_s`` [b, H, m] -> (out [b, g, H, D],
    lse [b, H, g]) with uncovered positions zero / NEG_INF, so they get zero
    weight in the cross-branch softmax fusion. The inverse of
    :func:`dense_to_sparse`: ``.at[...].set`` on static phase slices of the
    ``[b, m, r, H, D]`` view — static dynamic-update-slices, no scatter op.
    """
    b, m, H, Dh = out_s.shape
    if ratio == 1:
        return out_s[:, :seg_len], lse_s[..., :seg_len]
    out_d5 = jnp.zeros((b, m, ratio, H, Dh), out_s.dtype)
    lse_d5 = jnp.full((b, H, m, ratio), NEG_INF, lse_s.dtype)
    for p, hs, he in _phase_head_ranges(H, ratio):
        out_d5 = out_d5.at[:, :, p, hs:he, :].set(out_s[:, :, hs:he, :])
        lse_d5 = lse_d5.at[:, hs:he, :, p].set(lse_s[:, hs:he, :])
    out_d = out_d5.reshape(b, m * ratio, H, Dh)
    lse_d = lse_d5.reshape(b, H, m * ratio)
    return out_d[:, :seg_len], lse_d[..., :seg_len]


def _branch_kvlen_bhld(
    num_heads: int, n_seg: int, g: int, ratio: int, m: int, real_len: int
) -> Optional[np.ndarray]:
    """Static [H, n_seg] valid sparse-key counts for the head-major branch.

    Sparse slot ``j`` of segment ``s`` / head ``h`` maps to dense position
    ``s*g + phase(h) + ratio*j``; it is valid iff that position is a real
    token (< real_len) *and* falls inside the segment's own ``g`` dense slots
    (per-segment alignment padding beyond ``g`` belongs to no token).
    Returns None when every slot is valid. Trace-time constants: free under
    jit, and fully-padded key blocks are skipped by the kernel.
    """
    heads_per_group = -(-num_heads // ratio)
    phases = np.arange(num_heads) // heads_per_group  # [H]
    seg = np.arange(n_seg)[None, :]  # [1, n_seg]
    in_seg = np.clip(real_len - seg * g, 0, g)  # real dense tokens in segment
    counts = np.ceil((in_seg - phases[:, None]) / ratio)
    counts = np.clip(counts, 0, m).astype(np.int32)  # [H, n_seg]
    if (counts == m).all():
        return None
    return counts


def _dilate_bhld(x: jnp.ndarray, ratio: int) -> jnp.ndarray:
    """[B, H, n, gp, D] -> [B, H, n, gp/r, D] dilated subsample, head-phased.

    Same phase-slice trick as :func:`dense_to_sparse`, on the head-major
    layout: view the per-segment axis as (m, r) and take each phase's head
    range — all static slices.
    """
    if ratio == 1:
        return x
    B, H, n, gp, Dh = x.shape
    assert gp % ratio == 0, (gp, ratio)
    m = gp // ratio
    x6 = x.reshape(B, H, n, m, ratio, Dh)
    parts = [x6[:, hs:he, :, :, p, :] for p, hs, he in _phase_head_ranges(H, ratio)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)


def _undilate_bhld(
    out_s: jnp.ndarray, lse_s: jnp.ndarray, ratio: int
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Inverse of :func:`_dilate_bhld`: sparse [B, H, n, m, D] back to dense
    [B, H, n, m*r, D] (+ lse [B, H, n, m*r]), uncovered slots zero / NEG_INF.

    One fused broadcast-select against a static [H, r] phase mask (a
    per-phase ``.at[].set`` loop re-copies the full dense buffer per phase —
    ~r x the write traffic)."""
    B, H, n, m, Dh = out_s.shape
    if ratio == 1:
        return out_s, lse_s
    # [H, r] phase mask built from iotas on-device: a host constant here
    # shows up as a per-step pred[] DMA in profiles
    h_idx = jax.lax.broadcasted_iota(jnp.int32, (H, ratio), 0)
    p_idx = jax.lax.broadcasted_iota(jnp.int32, (H, ratio), 1)
    mask = (h_idx // -(-H // ratio)) == p_idx  # [H, r]
    out_d = jnp.where(
        mask[None, :, None, None, :, None], out_s[:, :, :, :, None, :], 0
    )
    lse_d = jnp.where(mask[None, :, None, None, :], lse_s[..., None], NEG_INF)
    return out_d.reshape(B, H, n, m * ratio, Dh), lse_d.reshape(B, H, n, m * ratio)


@jax.named_scope("kernel_fwd")
def _segment_attention_jnp(
    q5: jnp.ndarray, k5: jnp.ndarray, v5: jnp.ndarray, kvlen, is_causal: bool
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense (out, lse) attention on the segment-batched head-major layout
    [B, H, S, M, D] — the fallback tier for short segments / non-TPU runs,
    numerically matching the Pallas kernel (fp32 softmax, masked rows -> 0)."""
    B, H, S, M, Dh = q5.shape
    scale = Dh ** -0.5
    s = jnp.einsum(
        "bhsqd,bhskd->bhsqk", q5, k5, preferred_element_type=jnp.float32
    ).astype(jnp.float32) * scale
    mask = None
    if kvlen is not None:
        lens = jnp.asarray(kvlen, jnp.int32).reshape(-1, H, S)
        mask = jnp.arange(k5.shape[3])[None, None, None, None, :] >= lens[..., None, None]
        s = jnp.where(mask, NEG_INF, s)
    if is_causal:
        qi = jnp.arange(M)[:, None] + (k5.shape[3] - M)
        ki = jnp.arange(k5.shape[3])[None, :]
        s = jnp.where(ki > qi, NEG_INF, s)
    lse = jax.scipy.special.logsumexp(s, axis=-1)  # [B, H, S, M]
    p = jnp.exp(s - lse[..., None])
    if mask is not None:
        p = jnp.where(mask, 0.0, p)
    out = jnp.einsum(
        "bhsqk,bhskd->bhsqd", p.astype(v5.dtype), v5,
        preferred_element_type=jnp.float32,
    ).astype(q5.dtype)
    return out, lse


def _bhld_geom(L: int, sl: int, r: int) -> Tuple[int, int, int, int, int, int]:
    """(g, Lp, n, gp, m, block) for one head-major branch."""
    g = min(sl, L)
    Lp = _round_up(L, g)
    n = Lp // g
    gp = _round_up(g, r)
    m = gp // r
    # Single-block-if-it-fits: a sparse length like m=1281 under fixed
    # 1024 blocks pads both q and k to 2048 (2.6x the intrinsic MXU work);
    # one 1408-square block wastes 10% per side and streams K/V exactly
    # once. The 1408 cap keeps the fp32 logits tile (block^2 = 7.9 MB)
    # plus stats/blocks inside the 16 MB VMEM.
    single = _round_up(m, 128)
    block = single if single <= 1408 else min(1024, single)
    return g, Lp, n, gp, m, block


@jax.named_scope("dilate")
def _seg_dilate(x: jnp.ndarray, g: int, Lp: int, n: int, gp: int, r: int) -> jnp.ndarray:
    """[B, H, L, D] -> dilated segment view [B, H, n, m, D] (static slices)."""
    B, H, L, Dh = x.shape
    if Lp != L:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, Lp - L), (0, 0)))
    x = x.reshape(B, H, n, g, Dh)
    if gp != g:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, gp - g), (0, 0)))
    return _dilate_bhld(x, r)


@jax.named_scope("undilate")
def _undilate_to_dense(out_s, lse_s, r, g, Lp, L):
    B, H = out_s.shape[:2]
    Dh = out_s.shape[-1]
    out_d, lse_d = _undilate_bhld(out_s, lse_s, r)  # [B, H, n, gp, D]
    out = out_d[:, :, :, :g].reshape(B, H, Lp, Dh)[:, :, :L]
    lse = lse_d[:, :, :, :g].reshape(B, H, Lp)[:, :, :L]
    return out, lse


def _bhld_kvlen(
    B: int, H: int, n: int, g: int, r: int, m: int, real_len: int,
    valid_len_dyn: Optional[jnp.ndarray],
) -> Optional[jnp.ndarray]:
    """[B, H, n] int32 valid sparse-key counts, or None when every slot is
    valid: static tail masks (alignment padding, ``real_len``) combined
    with the optional *traced* per-batch suffix valid lengths (collate pad
    masks) by minimum. Traced counts keep the Pallas path: the kernels
    read them from SMEM at runtime.

    The traced block mirrors the numpy formula of
    :func:`_branch_kvlen_bhld` (sparse slot j of head phase p is valid iff
    dense position ``p + r*j`` lies inside both the segment and the valid
    prefix) — keep the two in lockstep;
    ``test_traced_valid_len_matches_generic`` guards the equivalence."""
    static = _branch_kvlen_bhld(H, n, g, r, m, real_len)
    if static is None and valid_len_dyn is None:
        return None  # all slots valid: lets the jnp tier skip masking
    if static is None:
        static = np.full((H, n), m, np.int32)
    kv = jnp.asarray(np.broadcast_to(static[None], (B, H, n)))
    if valid_len_dyn is not None:
        heads_per_group = -(-H // r)
        phases = jnp.arange(H) // heads_per_group  # [H]: per-head phase id
        kv = jnp.minimum(
            kv, dyn_sparse_counts(valid_len_dyn, g, r, m, phases, n)
        )
    return kv


def dyn_sparse_counts(
    valid_dyn: jnp.ndarray, g: int, r: int, m: int, phases: jnp.ndarray,
    n_seg: int,
) -> jnp.ndarray:
    """[B, len(phases), n_seg] int32 valid sparse-key counts from TRACED
    per-batch valid lengths: sparse slot j of phase p is valid iff dense
    position ``seg*g + p + r*j`` lies inside both the segment and the
    valid prefix. The ONE dynamic-masking formula — shared by the
    head-major tier (phases = per-head phase ids) and the fused
    phase-major tier (phases = arange(r)); keep callers on it so the two
    kernel families can never disagree on boundary semantics."""
    seg = jnp.arange(n_seg)
    in_seg = jnp.clip(
        valid_dyn.reshape(-1)[:, None] - seg[None] * g, 0, g
    )  # [B, n_seg]
    counts = jnp.ceil((in_seg[:, None, :] - phases[None, :, None]) / r)
    return jnp.clip(counts, 0, m).astype(jnp.int32)


def _normalize_valid_len(valid_len, B: int, L: int):
    """(real_len static int, valid_dyn traced [B] or None) from the public
    ``valid_len`` contract: None = all valid, int = static suffix bound
    (folds into trace-time masks), array = TRACED per-batch suffix valid
    lengths (ride the kernels' SMEM valid-count tables at runtime)."""
    if valid_len is None:
        return L, None
    if isinstance(valid_len, (int, np.integer)):
        return min(int(valid_len), L), None
    return L, jnp.asarray(valid_len).reshape(B)


def _flat_eligible(g: int, r: int) -> bool:
    """True when an undilated branch takes the flat zero-glue kernel path
    instead of the segmented one. The single dispatch predicate — also
    consumed by scripts/tpu_selfcheck.py's kernel-coverage dedup key, which
    must compile exactly the kernel variants this choice selects."""
    from gigapath_tpu.ops.pallas_flash import FLAT_MAX_SEGMENT

    return r == 1 and g % 8 == 0 and g <= FLAT_MAX_SEGMENT


def _branch_pallas_fwd_impl(qh, kh, vh, kvlen, sl, r, is_causal, interpret):
    from gigapath_tpu.ops import pallas_flash as pf

    B, H, L, Dh = qh.shape
    g, Lp, n, gp, m, block = _bhld_geom(L, sl, r)
    q5 = _seg_dilate(qh, g, Lp, n, gp, r)
    k5 = _seg_dilate(kh, g, Lp, n, gp, r)
    v5 = _seg_dilate(vh, g, Lp, n, gp, r)
    out_s, lse_s = pf._fwd_impl(
        q5, k5, v5, kvlen, is_causal, Dh ** -0.5, block, block, interpret
    )
    return _undilate_to_dense(out_s, lse_s, r, g, Lp, L)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _branch_pallas(qh, kh, vh, kvlen, sl, r, is_causal, interpret):
    """One head-major dilated branch -> dense (out [B,H,L,D], lse [B,H,L]).

    The custom VJP sits at the BRANCH level, above the dilation: residuals
    are the UNDILATED q/k/v (shared buffers across every branch of the
    multi-branch op — XLA stores one copy) plus this branch's dense
    (out, lse). The flash-level VJP instead saved per-branch dilated
    q5/k5/v5 copies: on the flagship's 5-branch schedule that is ~15 extra
    [B, H, L, 48]-sized residual tensors per layer, the dominant train-step
    memory at PANDA-scale N (measured 53 GB at the 16k bucket; 12.4 GB
    here). Backward re-dilates with the same static slices — a bandwidth
    pass, no extra kernel work. ``kvlen`` [B, H, n] may be traced.
    """
    out, lse = _branch_pallas_fwd_impl(
        qh, kh, vh, kvlen, sl, r, is_causal, interpret
    )
    return out, lse


def _branch_pallas_fwd(qh, kh, vh, kvlen, sl, r, is_causal, interpret):
    out, lse = _branch_pallas_fwd_impl(
        qh, kh, vh, kvlen, sl, r, is_causal, interpret
    )
    return (out, lse), (qh, kh, vh, kvlen, out, lse)


def _branch_pallas_bwd(sl, r, is_causal, interpret, res, cots):
    from gigapath_tpu.ops import pallas_flash as pf

    qh, kh, vh, kvlen, out, lse = res
    do, _dlse = cots  # dense [B, H, L, D]; no gradient through the lse
    B, H, L, Dh = qh.shape
    g, Lp, n, gp, m, block = _bhld_geom(L, sl, r)
    # re-dilate the inputs + the dense cotangent/out/lse into the kernel
    # layout (static slices; the rank-3 lse/delta ride a trailing unit dim)
    q5 = _seg_dilate(qh, g, Lp, n, gp, r)
    k5 = _seg_dilate(kh, g, Lp, n, gp, r)
    v5 = _seg_dilate(vh, g, Lp, n, gp, r)
    do5 = _seg_dilate(do, g, Lp, n, gp, r)
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    delta5 = _seg_dilate(delta[..., None], g, Lp, n, gp, r)[..., 0]
    lse5 = _seg_dilate(lse[..., None], g, Lp, n, gp, r)[..., 0]
    # Backward blocks are chosen independently of the forward single block:
    # the bwd kernels hold ~2.5 live fp32 logits tiles (vs the forward's
    # ~2), so the forward's 1408 choice overflows scoped vmem in the
    # backward (the round-3 driver crash). bwd_blocks keeps block_q = the
    # forward block (q side stays unpadded) and shrinks block_k to fit.
    bq, bk = pf.bwd_blocks(block)
    dq5, dk5, dv5 = pf._bwd_impl(
        q5, k5, v5, lse5, delta5, do5, kvlen, is_causal, Dh ** -0.5,
        bq, bk, interpret,
    )

    def undo(g5):
        dense, _ = _undilate_to_dense(g5, jnp.zeros(g5.shape[:-1], jnp.float32),
                                      r, g, Lp, L)
        return dense

    kvlen_ct = (
        None if kvlen is None else np.zeros(kvlen.shape, dtype=jax.dtypes.float0)
    )
    return undo(dq5), undo(dk5), undo(dv5), kvlen_ct


_branch_pallas.defvjp(_branch_pallas_fwd, _branch_pallas_bwd)


def _branch_bhld(
    qh: jnp.ndarray,
    kh: jnp.ndarray,
    vh: jnp.ndarray,
    sl: int,
    r: int,
    *,
    is_causal: bool,
    real_len: int,
    interpret: bool,
    use_pallas: Optional[bool],
    valid_len_dyn: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One dilated branch, entirely in [B, H, L, D]: segment via a free
    reshape, dilate via static phase slices, run the segment-grid flash
    kernel, and undo — no batch-axis reshuffling or relayouts anywhere."""
    B, H, L, Dh = qh.shape
    g, Lp, n, gp, m, block = _bhld_geom(L, sl, r)

    if use_pallas is None:
        from gigapath_tpu.ops.flash_attention import PALLAS_MIN_SEQ, _on_tpu

        use_pallas = (interpret or _on_tpu()) and m >= PALLAS_MIN_SEQ

    if use_pallas and valid_len_dyn is None and _flat_eligible(g, r):
        from gigapath_tpu.ops.pallas_flash import flat_segment_flash

        # undilated branch on the FLAT arrays: no pads, reshapes,
        # dilation, or scatter-back — the ragged tail rides Pallas OOB
        # auto-masking + the per-segment kvlen select. This removes the
        # branch's entire XLA glue (the L -> round_up(L, g) pad alone
        # copied the whole tensor, ~0.12 ms each for q/k/v at L=10k).
        return flat_segment_flash(
            qh, kh, vh, segment_len=g, real_len=real_len,
            is_causal=is_causal, interpret=interpret,
        )

    kvlen = _bhld_kvlen(B, H, n, g, r, m, real_len, valid_len_dyn)
    if use_pallas:
        return _branch_pallas(qh, kh, vh, kvlen, sl, r, is_causal, interpret)

    q5 = _seg_dilate(qh, g, Lp, n, gp, r)
    k5 = _seg_dilate(kh, g, Lp, n, gp, r)
    v5 = _seg_dilate(vh, g, Lp, n, gp, r)
    out_s, lse_s = _segment_attention_jnp(q5, k5, v5, kvlen, is_causal)
    return _undilate_to_dense(out_s, lse_s, r, g, Lp, L)


@jax.named_scope("dilated_attn")
def dilated_attention_fused(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_lengths: Sequence[int],
    dilated_ratios: Sequence[int],
    *,
    is_causal: bool = False,
    valid_len=None,
    streaming_fusion: Optional[bool] = None,
    interpret: bool = False,
    flags=None,
) -> jnp.ndarray:
    """Fastest path: per-branch phase-major Pallas kernels on dense
    [B, L, E] activations (see :mod:`gigapath_tpu.ops.pallas_dilated`).

    ``flags``: one :class:`~gigapath_tpu.ops.pallas_dilated.PipelineFlags`
    snapshot shared by every branch of this op (None: snapshot the
    environment here, once). ``flags.stream_fusion``
    (``GIGAPATH_STREAM_FUSION``) routes the whole op through the
    streaming fusion epilogue: branch results stay in the packed
    phase-major layout end to end and one epilogue kernel chain emits the
    fused dense output — the per-branch dense out/lse scatter (the
    round-4 glue) never runs. The dense scatter + stacked-softmax path
    below remains the fallback and the parity oracle.

    ``streaming_fusion``: fold each branch's (out, lse) into running
    (acc, m, l) instead of stacking all branch outputs (None — the
    default — inherits ``flags.streaming_fusion``; an
    explicit bool pins the choice) — each branch's
    packed temporaries AND its dense output die before the next branch
    computes, the peak-memory requirement for long-context forwards. All
    streaming state is 128-lane-clean here ([B, L, E] fp32 acc, [B, H, L]
    stats), unlike the head-major variant whose accumulator had to stay in
    the branch's padded layout to preserve XLA fusion.

    Activations never leave the 128-lane-aligned ``[B, L, E]`` layout:
    segmenting and dilation ride the kernels' BlockSpec index maps, each
    branch emits a dense ``(out [B,L,E], lse [B,H,L])`` pair, and the
    cross-branch LSE-softmax fusion is one fused elementwise pass. Branches
    whose ratio does not divide the head count (never the case for LongNet's
    power-of-two schedules) fall back to the head-major path.
    """
    from gigapath_tpu.ops.pallas_dilated import (
        dilated_attention_stream_fused,
        dilated_branch_attention,
        plan_stream_fusion,
        snapshot_flags,
    )

    B, L, H, Dh = q.shape
    E = H * Dh
    if flags is None:
        flags = snapshot_flags()
    if streaming_fusion is None:
        streaming_fusion = flags.streaming_fusion
    qE, kE, vE = (x.reshape(B, L, E) for x in (q, k, v))
    real_len, valid_dyn = _normalize_valid_len(valid_len, B, L)

    if flags.stream_fusion and len(segment_lengths) > 1:
        plan = plan_stream_fusion(
            L, E, H, segment_lengths, dilated_ratios, interpret=interpret,
        )
        if plan is not None:
            out = dilated_attention_stream_fused(
                qE, kE, vE, segment_lengths, dilated_ratios, H,
                real_len=real_len, valid_len_dyn=valid_dyn,
                is_causal=is_causal, interpret=interpret, flags=flags,
                plan=plan,
            )
            return out.reshape(B, L, H, Dh)
        # visible, once per schedule: the epilogue silently not engaging
        # would otherwise be indistinguishable from it being slow
        _warn_once(
            "GIGAPATH_STREAM_FUSION requested but schedule %s/%s at L=%d "
            "admits no epilogue blocking (ratio not dividing H=%d/E=%d, or "
            "no legal dense-block alignment): using the dense fusion path"
            % (list(segment_lengths), list(dilated_ratios), L, H, E)
        )

    def branch(sl, r):
        sl, r = int(sl), int(r)
        with jax.named_scope(f"branch_r{r}"):
            if H % r == 0 and E % r == 0:
                return dilated_branch_attention(
                    qE, kE, vE, sl, r, H,
                    real_len=real_len, valid_len_dyn=valid_dyn,
                    is_causal=is_causal, interpret=interpret, flags=flags,
                )
            qh, kh, vh = (x.transpose(0, 2, 1, 3) for x in (q, k, v))
            o4, l = _branch_bhld(
                qh, kh, vh, sl, r, is_causal=is_causal, real_len=real_len,
                interpret=interpret, use_pallas=None, valid_len_dyn=valid_dyn,
            )
            return o4.transpose(0, 2, 1, 3).reshape(B, L, E), l

    if streaming_fusion and len(segment_lengths) > 1:
        # Online softmax over the branch axis (same math as the stacked
        # fusion below; weights constant in backward via stop_gradient).
        # Everything that lives ACROSS branches is lane-clean: acc is the
        # [B, L, H, Dh] view of [B, L, E] fp32 and the running stats stay
        # [B, H, L] (L on lanes); their transposed broadcasts inside the
        # update are fused temps.
        def bLH1(x):  # [B, H, L] -> broadcastable [B, L, H, 1] view
            return x.transpose(0, 2, 1)[..., None]

        acc = m_run = l_run = None
        for sl, r in zip(segment_lengths, dilated_ratios):
            o, l = branch(sl, r)
            with jax.named_scope("merge"):
                l = jax.lax.stop_gradient(l)  # [B, H, L]
                o = o.reshape(B, L, H, Dh)
                if acc is None:
                    m_run = l
                    l_run = jnp.ones_like(l)
                    acc = o.astype(jnp.float32)
                else:
                    m_new = jnp.maximum(m_run, l)
                    a = jnp.exp(m_run - m_new)
                    b_ = jnp.exp(l - m_new)
                    l_run = l_run * a + b_
                    acc = acc * bLH1(a) + o.astype(jnp.float32) * bLH1(b_)
                    m_run = m_new
        with jax.named_scope("merge"):
            return (acc / bLH1(l_run)).astype(q.dtype)

    outs, lses = [], []
    for sl, r in zip(segment_lengths, dilated_ratios):
        o, l = branch(sl, r)
        outs.append(o)
        lses.append(l)

    if len(outs) == 1:
        out = outs[0]
    else:
        with jax.named_scope("merge"):
            lse = jnp.stack(lses)  # [n_branch, B, H, L]
            weights = jax.nn.softmax(jax.lax.stop_gradient(lse), axis=0)
            acc = 0.0
            for o, w in zip(outs, weights):
                # w [B,H,L] -> [B,L,H,1] broadcast over the head's lanes;
                # the whole fusion is one elementwise pass over the branch
                # outputs
                acc = acc + o.reshape(B, L, H, Dh).astype(jnp.float32) * (
                    w.transpose(0, 2, 1)[..., None]
                )
            out = acc.reshape(B, L, E)
    return out.astype(q.dtype).reshape(B, L, H, Dh)


@jax.named_scope("dilated_attn")
def dilated_attention_bhld(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_lengths: Sequence[int],
    dilated_ratios: Sequence[int],
    *,
    is_causal: bool = False,
    valid_len=None,
    interpret: bool = False,
    use_pallas: Optional[bool] = None,
    streaming_fusion: bool = False,
) -> jnp.ndarray:
    """Head-major fast path for multi-branch dilated attention.

    Same math as :func:`dilated_attention` (same branch schedule, same
    LSE-softmax fusion with stop-gradient weights), restructured for TPU
    memory layout: one [B,L,H,D] -> [B,H,L,D] relayout at entry, one at
    exit, and every per-branch step in between — segmenting, dilation,
    attention, scatter-back, fusion — is a free reshape, a static slice, or
    a segment-grid Pallas kernel. The per-branch transposes of the generic
    path (3 inputs + out + lse per branch, 5 branches in the flagship) are
    gone. ``valid_len``: suffix-padding bound — a static int (alignment
    padding) folds into trace-time masks; a *traced* [B] array (collate pad
    masks) rides the kernels' SMEM valid-count tables at runtime, keeping
    the Pallas path for masked batches.
    """
    B, L, H, Dh = q.shape
    real_len, valid_dyn = _normalize_valid_len(valid_len, B, L)
    # optimization barriers pin the op's boundaries: without them XLA fuses
    # the entry/exit relayouts into the surrounding layernorm/projection
    # fusions, which then read the 48-lane-minor head-major layout strided
    # (measured +0.65 ms/layer on the flagship, PERFORMANCE.md)
    q, k, v = jax.lax.optimization_barrier((q, k, v))
    qh = q.transpose(0, 2, 1, 3)
    kh = k.transpose(0, 2, 1, 3)
    vh = v.transpose(0, 2, 1, 3)

    if streaming_fusion and len(segment_lengths) > 1:
        # Online softmax over the BRANCH axis: each branch's (out, lse) is
        # folded into running (acc, m, l) and its buffers die before the
        # next branch computes — the stacked fusion below keeps all
        # n_branch dense outputs live simultaneously, which dominates peak
        # HBM at PANDA-scale N (the 1M-token operating point). Identical
        # math: final = sum_b softmax_b(lse)[b] * out_b, weights constant
        # in backward (stop_gradient, parity with reference torch.no_grad).
        #
        # Layout note (round 4, measured): keeping the accumulator in the
        # branch layout [B, H, L, D] lets XLA fuse each branch's undilate
        # write directly into the online update — one pass, no extra
        # buffer. A lane-clean [B, L, H, D] accumulator (tried to shave
        # the 48->128 tile padding) materializes every branch output in
        # BOTH layouts and pushed 256k from 12.7 GB to an OOM at 15.9 GB.
        acc = m_run = l_run = None
        for sl, r in zip(segment_lengths, dilated_ratios):
            with jax.named_scope(f"branch_r{int(r)}"):
                o, l = _branch_bhld(
                    qh, kh, vh, int(sl), int(r),
                    is_causal=is_causal, real_len=real_len,
                    interpret=interpret, use_pallas=use_pallas,
                    valid_len_dyn=valid_dyn,
                )
            with jax.named_scope("merge"):
                l = jax.lax.stop_gradient(l)[..., None]  # [B, H, L, 1]
                if acc is None:
                    m_run = l
                    l_run = jnp.ones_like(l)
                    acc = o.astype(jnp.float32)
                else:
                    m_new = jnp.maximum(m_run, l)
                    a = jnp.exp(m_run - m_new)
                    b_ = jnp.exp(l - m_new)
                    l_run = l_run * a + b_
                    acc = acc * a + o.astype(jnp.float32) * b_
                    m_run = m_new
        with jax.named_scope("merge"):
            out = acc / l_run
        return jax.lax.optimization_barrier(
            out.astype(q.dtype).transpose(0, 2, 1, 3)
        )

    outs, lses = [], []
    for sl, r in zip(segment_lengths, dilated_ratios):
        with jax.named_scope(f"branch_r{int(r)}"):
            o, l = _branch_bhld(
                qh, kh, vh, int(sl), int(r),
                is_causal=is_causal, real_len=real_len,
                interpret=interpret, use_pallas=use_pallas,
                valid_len_dyn=valid_dyn,
            )
        outs.append(o)
        lses.append(l)

    if len(outs) == 1:
        out = outs[0]
    else:
        with jax.named_scope("merge"):
            lse = jnp.stack(lses)  # [n_branch, B, H, L]
            weights = jax.nn.softmax(jax.lax.stop_gradient(lse), axis=0)[..., None]
            out = sum(o.astype(jnp.float32) * w for o, w in zip(outs, weights))
    return jax.lax.optimization_barrier(
        out.astype(q.dtype).transpose(0, 2, 1, 3)
    )


def _gather_kv_seq_parallel(
    x: jnp.ndarray, sl: int, local_len: int, axis_name: str
) -> jnp.ndarray:
    """All-gather sparse K/V along the seq axis, keep the ranks of my segment.

    ``x`` [b, m, H, D] is the local (single-segment) sparse view; returns
    [b, m * ranks_per_segment, H, D]. Counterpart of reference
    ``gather_kv:55-74`` (non-causal path), with the autograd all-gather /
    reduce-scatter pair replaced by ``jax.lax.all_gather`` which is
    differentiable by construction.
    """
    assert sl % local_len == 0, (sl, local_len)
    ranks_per_segment = sl // local_len
    gathered = jax.lax.all_gather(x, axis_name, axis=0, tiled=False)  # [W, b, m, H, D]
    rank = jax.lax.axis_index(axis_name)
    segment_start = rank // ranks_per_segment * ranks_per_segment
    segment = jax.lax.dynamic_slice_in_dim(gathered, segment_start, ranks_per_segment, axis=0)
    # [rps, b, m, H, D] -> [b, rps*m, H, D]
    segment = segment.transpose(1, 0, 2, 3, 4)
    b = segment.shape[0]
    return segment.reshape(b, ranks_per_segment * segment.shape[2], *segment.shape[3:])


# ---------------------------------------------------------------------------
# ring-scheduled K/V exchange (GIGAPATH_RING_ATTN)
# ---------------------------------------------------------------------------
#
# The all-gather path above materializes every oversized branch's ENTIRE
# segment K/V on every shard — per-shard memory O(full segment), with the
# collective serial on the critical path. The ring schedule below (Ring
# Attention, Liu et al. 2023, arXiv:2310.01889) keeps per-shard memory
# O(local chunk): each shard holds only its own sparse K/V chunk, the
# chunks rotate around the segment's sub-ring via jax.lax.ppermute, each
# step computes partial attention of the LOCAL queries against the
# RESIDENT chunk, and partials fold through the stored-LSE online-softmax
# combine (flash_attention.combine_partials — the same merge primitive
# the stream-fusion epilogue applies across branches, here applied across
# ring steps). The next chunk's ppermute is issued BEFORE the resident
# chunk's compute, so the collective has no data dependence on the
# attention math and XLA can overlap it with kernel time. The gather path
# stays as the fallback and parity oracle.


def _ring_perm(world: int, rps: int) -> Tuple[Tuple[int, int], ...]:
    """Static ppermute (src, dst) pairs rotating every ``rps``-sized
    sub-ring of the seq axis by one: rank r sends to the next rank of ITS
    OWN segment's ring (``rps < world`` = several independent sub-rings,
    the segment-spans-a-strict-subset-of-the-mesh case). After s
    applications, rank r holds the chunk of rank
    ``(r // rps) * rps + (r % rps - s) % rps``."""
    assert world % rps == 0, (world, rps)
    return tuple(
        (src, (src // rps) * rps + ((src % rps) + 1) % rps)
        for src in range(world)
    )


def _ring_step_counts(counts, my_rel, s: int, rps: int):
    """Valid-key counts [B, H] for ring step ``s``: the row of the
    per-origin-rank table [rps, B, H] belonging to the chunk resident at
    step s (origin ``(my_rel - s) mod rps``, a traced index — the counts
    stay in the table and the step selects its row, so the hoisted gather
    is shared by every step of every gathered branch)."""
    if counts is None:
        return None
    orig = jnp.mod(my_rel - s, rps)
    return jax.lax.dynamic_slice_in_dim(counts, orig, 1, axis=0)[0]


def _ring_attention_fwd_impl(qs, ks, vs, counts, axis_name, world, rps,
                             allow_pallas):
    """Forward ring: local sparse q [B, mq, H, D] against the rotating
    chunks [B, mk, H, D] -> (out [B, mq, H, D], lse [B, H, mq])."""
    from gigapath_tpu.obs.spans import ring_step
    from gigapath_tpu.ops.flash_attention import (
        combine_partials,
        partial_attention,
    )

    perm = _ring_perm(world, rps)
    my_rel = jnp.mod(jax.lax.axis_index(axis_name), rps)
    comm_bytes = 2 * int(np.prod(ks.shape)) * ks.dtype.itemsize  # k + v
    use_pallas = None if allow_pallas else False
    out = lse = None
    k_cur, v_cur = ks, vs
    for s in range(rps):
        with ring_step(s, rps, comm_bytes if s + 1 < rps else 0):
            # double-buffer: the permute reads only the resident chunk,
            # never this step's attention results — issued first, it can
            # ride the interconnect while the partial attention computes
            if s + 1 < rps:
                k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
                v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            cnt = _ring_step_counts(counts, my_rel, s, rps)
            o_s, l_s = partial_attention(
                qs, k_cur, v_cur, kv_valid_len=cnt, use_pallas=use_pallas
            )
            if out is None:
                # fp32 accumulator from the first partial on: every later
                # combine_partials keeps it fp32 (out_a's dtype)
                out, lse = o_s.astype(jnp.float32), l_s
            else:
                out, lse = combine_partials(out, lse, o_s, l_s)
            if s + 1 < rps:
                k_cur, v_cur = k_nxt, v_nxt
    return out.astype(qs.dtype), lse


def _ring_partial_bwd(qs, k_c, v_c, do, lse, delta, cnt, scale):
    """One ring step's gradient contributions, flash-backward style: the
    chunk's probabilities are recomputed from the logits and the FINAL
    combined lse (p = exp(s - lse_full) is already the full-softmax
    probability restricted to this chunk's keys), so no per-step
    normalization state needs saving. All math fp32; numerics mirror
    attention_with_lse (mask before lse-subtract, masked probs zeroed)."""
    q32 = qs.astype(jnp.float32)
    k32 = k_c.astype(jnp.float32)
    v32 = v_c.astype(jnp.float32)
    do32 = do.astype(jnp.float32)
    s_ = jnp.einsum("bqhd,bkhd->bhqk", q32, k32) * scale
    if cnt is not None:
        col_ok = (
            jnp.arange(k_c.shape[1])[None, None, None, :]
            < cnt[:, :, None, None]
        )
        s_ = jnp.where(col_ok, s_, NEG_INF)
    p = jnp.exp(s_ - lse[..., None])  # [B, H, mq, mk]
    if cnt is not None:
        p = jnp.where(col_ok, p, 0.0)
    dv = jnp.einsum("bhqk,bqhd->bkhd", p, do32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", do32, v32)
    ds = p * (dp - delta[..., None])
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds, k32) * scale
    dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q32) * scale
    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7))
def _ring_attention(qs, ks, vs, counts, axis_name, world, rps, allow_pallas):
    """Ring-scheduled attention of local sparse queries against the
    segment's rotating sparse K/V chunks.

    ``qs`` [B, mq, H, D] local queries; ``ks``/``vs`` [B, mk, H, D] the
    LOCAL chunk (never gathered); ``counts`` optional [rps, B, H] valid
    sparse-key counts per ORIGIN rank of the sub-ring (from the hoisted
    per-call counts gather), or None when every slot is valid. Returns
    ``(out [B, mq, H, D], lse [B, H, mq])`` — identical math to
    attending the concatenated chunks (softmax is associative under the
    stored-LSE combine), so the all-gather path stays the parity oracle.

    The custom VJP rings in reverse order of memory, not of schedule:
    the same forward rotation replays, each shard computes its
    contribution to the RESIDENT chunk's dK/dV from the saved combined
    lse (no per-step softmax state is stored), accumulates it into a
    gradient buffer that rotates WITH the chunk, and after a full cycle
    every buffer arrives home holding all ``rps`` shards' contributions
    — the overlapped twin of the differentiable all-gather's implicit
    backward reduce-scatter.
    """
    return _ring_attention_fwd_impl(
        qs, ks, vs, counts, axis_name, world, rps, allow_pallas
    )


def _ring_attention_fwd(qs, ks, vs, counts, axis_name, world, rps,
                        allow_pallas):
    out, lse = _ring_attention_fwd_impl(
        qs, ks, vs, counts, axis_name, world, rps, allow_pallas
    )
    # residuals: the local inputs plus the combined (out, lse) — nothing
    # whose size scales with the segment, and no per-step state
    return (out, lse), (qs, ks, vs, counts, out, lse)


def _ring_attention_bwd(axis_name, world, rps, allow_pallas, res, cots):
    from gigapath_tpu.obs.spans import ring_step

    qs, ks, vs, counts, out, lse = res
    do, _dlse = cots  # no gradient flows through the lse output
    Dh = qs.shape[-1]
    scale = Dh ** -0.5
    perm = _ring_perm(world, rps)
    my_rel = jnp.mod(jax.lax.axis_index(axis_name), rps)
    kv_bytes = 2 * int(np.prod(ks.shape)) * ks.dtype.itemsize  # k + v
    # delta = rowsum(do * out) per (token, head) — constant across steps
    delta = jnp.einsum(
        "bqhd,bqhd->bhq", do.astype(jnp.float32), out.astype(jnp.float32)
    )
    dq = jnp.zeros(qs.shape, jnp.float32)
    dk_acc = jnp.zeros(ks.shape, jnp.float32)
    dv_acc = jnp.zeros(vs.shape, jnp.float32)
    k_cur, v_cur = ks, vs
    # every step rotates the fp32 dk/dv accumulators; all but the last
    # also rotate the k/v double-buffer
    acc_bytes = 2 * int(np.prod(ks.shape)) * 4
    for s in range(rps):
        with ring_step(
            s, rps, acc_bytes + (kv_bytes if s + 1 < rps else 0)
        ):
            if s + 1 < rps:  # double-buffer: permute before the compute
                k_nxt = jax.lax.ppermute(k_cur, axis_name, perm)
                v_nxt = jax.lax.ppermute(v_cur, axis_name, perm)
            cnt = _ring_step_counts(counts, my_rel, s, rps)
            dq_s, dk_s, dv_s = _ring_partial_bwd(
                qs, k_cur, v_cur, do, lse, delta, cnt, scale
            )
            dq = dq + dq_s
            # dK/dV accumulate where computed and rotate WITH the chunk:
            # after the final step's permute each buffer is home (rotated
            # rps times == identity) carrying every shard's contribution
            dk_acc = jax.lax.ppermute(dk_acc + dk_s, axis_name, perm)
            dv_acc = jax.lax.ppermute(dv_acc + dv_s, axis_name, perm)
            if s + 1 < rps:
                k_cur, v_cur = k_nxt, v_nxt
    counts_ct = (
        None if counts is None
        else np.zeros(counts.shape, dtype=jax.dtypes.float0)
    )
    return (
        dq.astype(qs.dtype), dk_acc.astype(ks.dtype),
        dv_acc.astype(vs.dtype), counts_ct,
    )


_ring_attention.defvjp(_ring_attention_fwd, _ring_attention_bwd)


def dilated_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    segment_lengths: Sequence[int],
    dilated_ratios: Sequence[int],
    *,
    is_causal: bool = False,
    offset: int = 0,
    attn_fn: Optional[AttnFn] = None,
    seq_axis_name: Optional[str] = None,
    seq_axis_size: int = 1,
    dropout_rate: float = 0.0,
    dropout_rng: Optional[jax.Array] = None,
    valid_len: Optional[jnp.ndarray] = None,
    flags=None,
) -> jnp.ndarray:
    """Multi-branch dilated attention on [B, L, H, D] tensors -> [B, L, H, D].

    ``attn_fn(q, k, v, is_causal=...) -> (out, lse)`` defaults to the fused
    jnp op; pass the Pallas flash kernel for long dense segments. When
    ``seq_axis_name`` is set (inside ``shard_map``), L is the *local* shard
    length and branches whose segment exceeds it gather K/V across the axis;
    fully-local branches route through the fused phase-major kernels on TPU.
    shard_map callers must pass ``check_vma=False`` when the Pallas tier is
    active (vma checking cannot see through ``pallas_call``).
    ``dropout_rate`` is attention-probability dropout inside each branch
    (parity with the reference forwarding dropout to flash-attn).

    ``valid_len``: optional suffix-padding spec — tokens at positions
    ``>= valid_len`` are excluded from every branch's keys (the
    masked-batching extension the reference only sketches in its dead
    ``custom_*`` files). A static Python int (same for every row) folds into
    the trace-time tail masks; a traced [B] array (ragged batches) rides the
    Pallas kernels' runtime SMEM valid-count tables — both keep the compiled
    fast path. Under sequence parallelism ``valid_len`` is the LOCAL
    per-shard spec — an int bounds every shard's own suffix (correct for
    counts derived from the sharded mask, NOT for a global single-device
    bound carried into ``shard_map`` unchanged), and a traced [B] array is
    each shard's own valid count (sum the sharded ``key_padding_mask`` per
    shard, as :class:`DilatedAttention` does): segment-local branches
    consume it
    directly on the fused kernels, and gathered branches all-gather every
    rank's counts to mask the concatenated keys (global suffix padding
    keeps validity a contiguous prefix). A static int (same partial count
    on every shard — not a contiguous prefix) and causal + ``valid_len``
    both remain unsupported on gathered branches.

    ``flags``: one :class:`~gigapath_tpu.ops.pallas_dilated.PipelineFlags`
    snapshot shared by every branch of this op (None: snapshot the
    environment here, once — the same contract as
    :func:`dilated_attention_fused`). ``flags.ring_attn``
    (``GIGAPATH_RING_ATTN``) routes non-causal gathered branches through
    the ring-scheduled K/V exchange (:func:`_ring_attention`): per-shard
    memory O(local chunk) instead of O(full segment), ppermute overlapped
    with partial attention, the all-gather path remaining the fallback
    (causal gathered branches, custom ``attn_fn``, dropout) and the
    parity oracle.
    """
    attn_fn_was_default = attn_fn is None
    if attn_fn_was_default:
        from gigapath_tpu.ops.flash_attention import flash_attention

        attn_fn = flash_attention
    if dropout_rate > 0.0 and dropout_rng is not None:
        # attention-probability dropout requires materialized probs; the
        # default dispatcher is swapped for the jnp path (all gigapath
        # configs train with attention_dropout=0, so the flash kernel stays
        # on the hot path). An explicitly-supplied attn_fn is never silently
        # replaced.
        if not attn_fn_was_default:
            raise NotImplementedError(
                "attention dropout is not supported with a custom attn_fn"
            )
        base_fn = attention_with_lse
        rngs = jax.random.split(dropout_rng, len(segment_lengths))

        def make_attn_fn(branch_rng):
            return lambda *a, **kw: base_fn(
                *a, dropout_rate=dropout_rate, dropout_rng=branch_rng, **kw
            )
    assert len(segment_lengths) == len(dilated_ratios)
    if offset > 0 and k.shape[1] != offset + q.shape[1]:
        # incremental decoding contract (reference gathering:78-82): q holds
        # the new rows at global positions [offset, offset+Lq) and k/v hold
        # the full prefix-inclusive cache
        raise ValueError(
            f"offset={offset} decoding requires Lk == offset + Lq (full KV "
            f"cache); got Lq={q.shape[1]}, Lk={k.shape[1]}"
        )
    B, L, H, Dh = q.shape

    # ONE read of the environment per public call. Every branch of this
    # op — fused, head-major, gathered, ring — shares the snapshot, so
    # branches can never observe different dispatch decisions.
    if flags is None:
        from gigapath_tpu.ops.pallas_dilated import snapshot_flags

        flags = snapshot_flags()

    # ONE eligibility gate for the compiled-kernel paths (the single-device
    # fast path below and the seq-parallel fused-local routing further
    # down): no custom attn_fn, no dropout, no decoding offset, self-
    # attention shapes. Kept in one place so single-device and sharded
    # dispatch can never silently diverge.
    kernels_eligible = (
        attn_fn_was_default
        and not (dropout_rate > 0.0 and dropout_rng is not None)
        and offset == 0
        and q.shape == k.shape == v.shape
    )

    def _tpu_default_dispatch() -> bool:
        # escape hatch: GIGAPATH_FORCE_GENERIC_ATTN=1 re-routes the default
        # TPU dispatch to the generic jnp path (compiled-kernel triage aid;
        # the compiled kernels are otherwise validated by
        # scripts/tpu_selfcheck.py rather than the CPU/interpret CI tier)
        from gigapath_tpu.ops.flash_attention import _on_tpu

        return _on_tpu() and not _env_flag("GIGAPATH_FORCE_GENERIC_ATTN")

    # Head-major fast path (TPU): see dilated_attention_bhld. Taken whenever
    # nothing forces the generic layout and there is no sequence
    # parallelism. Both static AND traced valid_len ride this path (traced
    # counts live in the kernels' SMEM tables) — routing traced masks to
    # the generic jnp tier previously put the ENTIRE fine-tune train path
    # on dense-probability attention (53 GB at the 16k bucket).
    if kernels_eligible and (seq_axis_name is None or seq_axis_size <= 1):
        if _tpu_default_dispatch():
            # Phase-major fused path (pallas_dilated.py) is the default
            # since round 4's kernel-side packing landed: activations stay
            # [B, L, E], per-branch pack/unpack are single-pass Pallas copy
            # kernels over a diagonal-only layout, and the v5e op-time A/B
            # at N=10241 reads fused 5.19 ms vs head-major 6.69 ms forward
            # (grad step 15.1 vs 18.8 ms). Static AND traced valid_len both
            # ride it (traced counts live in the kernels' SMEM tables). The
            # head-major path remains for streaming branch fusion
            # (long-context memory) and ratios not dividing the heads.
            # flags.streaming_fusion (GIGAPATH_STREAMING_FUSION): fold
            # branches into running (acc, m, l) instead of stacking all
            # branch outputs — lower peak HBM, the enabler for the
            # 1M-token operating point. flags.stream_fusion
            # (GIGAPATH_STREAM_FUSION) engages the packed streaming
            # fusion epilogue inside dilated_attention_fused. Both ride
            # the ONE snapshot taken at the top of this call — no env
            # read happens here (gigalint GL017).
            streaming = flags.streaming_fusion
            fused_ok = all(
                H % int(rr) == 0 and (H * Dh) % int(rr) == 0
                for rr in dilated_ratios
            )
            if fused_ok:
                return dilated_attention_fused(
                    q, k, v, segment_lengths, dilated_ratios,
                    is_causal=is_causal, valid_len=valid_len,
                    streaming_fusion=streaming, flags=flags,
                )
            # visible, once per schedule: this fallback is a perf cliff
            # (head-major re-tiles activations per branch) that no log
            # line would otherwise ever attribute
            _warn_once(
                "dilated-attention schedule %s/%s has a ratio not dividing "
                "H=%d (or H*Dh=%d): falling back from the fused phase-major "
                "path to the head-major path"
                % (list(segment_lengths), list(dilated_ratios), H, H * Dh)
            )
            return dilated_attention_bhld(
                q, k, v, segment_lengths, dilated_ratios,
                is_causal=is_causal, valid_len=valid_len,
                streaming_fusion=streaming,
            )

    # Under sequence parallelism, branches whose segment fits the local
    # shard need no gather and are, per shard, exactly a single-device
    # branch — route them through the fused phase-major kernels (the
    # single-chip default path, measured 5.19 vs 6.69 ms fwd head-major at
    # N=10241) instead of the head-major generic loop. Gathered branches
    # and every non-default case keep the generic path.
    def _vma_transparent() -> bool:
        # vma checking cannot see through pallas_call: under a shard_map
        # with the default check_vma=True the traced avals carry a
        # non-empty vma and the kernel call would fail at trace time.
        # Fall back to the generic path there, warning once;
        # check_vma=False unlocks the fused routing (chip_smoke.py's
        # four-chip phase fails unless the sharded program holds the
        # kernels, so the fallback cannot pass for the kernel path).
        if jax.typeof(q).vma:
            _warn_once(
                "sequence-parallel dilated attention inside a "
                "check_vma=True shard_map: pallas kernels are vma-opaque, "
                "so local branches fall back to the generic path — pass "
                "check_vma=False to shard_map to enable the fused kernels"
            )
            return False
        return True

    # Ragged slides no longer force the generic fallback here: a traced
    # [B] valid_len (the module derives it from the SHARDED
    # key_padding_mask, so under shard_map it is the per-shard LOCAL
    # valid count) rides the fused kernels' SMEM valid-count tables
    # exactly as on a single device, and gathered branches combine the
    # all-gathered per-rank counts below (_dilated_branch).
    seq_active = seq_axis_name is not None and seq_axis_size > 1
    fused_local = (
        kernels_eligible
        and seq_active
        and _tpu_default_dispatch()
        and _vma_transparent()
    )
    sp_real_len, sp_valid_dyn = (
        _normalize_valid_len(valid_len, B, L) if fused_local else (L, None)
    )

    # Ring schedule (GIGAPATH_RING_ATTN) for the gathered branches: same
    # eligibility gate as the compiled kernels (default attn_fn, no
    # dropout, no offset, self-attention shapes) — the ring VJP implements
    # softmax-attention math and cannot honor an arbitrary attn_fn.
    # Causal gathered branches keep the gather path (its rank-bias
    # construction has no ring counterpart yet); _dilated_branch warns.
    ring_attn = bool(seq_active and kernels_eligible and flags.ring_attn)
    ring_allow_pallas = False
    if ring_attn:
        # flash_attention's Pallas tier for the per-step partials is only
        # reachable on TPU outside a vma-checking shard_map (same
        # constraint as the fused-local routing); the jnp tier is always
        # legal. Static: participates in the ring op's nondiff args.
        ring_allow_pallas = _tpu_default_dispatch() and _vma_transparent()

    # Hoisted per-call counts gather: the ragged valid counts are
    # rank-local data, identical across branches — ONE all_gather serves
    # every gathered branch (gather path and ring path alike) instead of
    # one per branch.
    gathered_counts = None
    if (
        seq_active
        and valid_len is not None
        and not isinstance(valid_len, (int, np.integer))
        and any(int(sl) > k.shape[1] for sl in segment_lengths)
    ):
        vl_local = jnp.asarray(valid_len, jnp.int32).reshape(B)
        gathered_counts = jax.lax.all_gather(
            vl_local, seq_axis_name, axis=0
        )  # [W, B]

    with jax.named_scope("dilated_attn"):
        outs, lses = [], []
        for i, (sl, r) in enumerate(zip(segment_lengths, dilated_ratios)):
            sl_i, r_i = int(sl), int(r)
            if seq_active and sl_i < k.shape[1] and k.shape[1] % sl_i:
                # each shard segments its own tokens from its own start: a
                # local segment that does not divide the shard puts segment
                # boundaries elsewhere than the unsharded op does
                _warn_once(
                    "sequence-parallel dilated attention: segment length %d "
                    "does not divide the %d-token shard, so this branch's "
                    "segments restart at every shard boundary and the result "
                    "differs from the unsharded op" % (sl_i, k.shape[1])
                )
            with jax.named_scope(f"branch_r{r_i}"):
                if (
                    fused_local
                    and sl_i <= k.shape[1]
                    and H % r_i == 0
                    and (H * Dh) % r_i == 0
                ):
                    from gigapath_tpu.ops.pallas_dilated import (
                        dilated_branch_attention,
                    )

                    oE, l = dilated_branch_attention(
                        q.reshape(B, L, H * Dh), k.reshape(B, L, H * Dh),
                        v.reshape(B, L, H * Dh), sl_i, r_i, H,
                        real_len=sp_real_len, valid_len_dyn=sp_valid_dyn,
                        is_causal=is_causal, flags=flags,
                    )
                    o = oE.reshape(B, L, H, Dh)
                else:
                    branch_fn = attn_fn
                    if dropout_rate > 0.0 and dropout_rng is not None:
                        branch_fn = make_attn_fn(rngs[i])
                    o, l = _dilated_branch(
                        q, k, v, sl_i, r_i,
                        is_causal=is_causal, offset=offset, attn_fn=branch_fn,
                        seq_axis_name=seq_axis_name,
                        seq_axis_size=seq_axis_size,
                        valid_len=valid_len, gathered_counts=gathered_counts,
                        ring=ring_attn, ring_allow_pallas=ring_allow_pallas,
                    )
            outs.append(o)
            lses.append(l)

        if len(outs) == 1:
            return outs[0]

        # LSE-weighted fusion across branches; weights are constants in
        # backward (parity with reference scattering:119-128 under
        # torch.no_grad).
        with jax.named_scope("merge"):
            lse = jnp.stack(lses)  # [n, B, H, L]
            weights = jax.nn.softmax(jax.lax.stop_gradient(lse), axis=0)
            out = sum(
                o.astype(jnp.float32) * w[..., None].transpose(0, 2, 1, 3)  # [B,H,L,1]->[B,L,H,1]
                for o, w in zip(outs, weights)
            )
            return out.astype(q.dtype)

def _dilated_branch(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    sl: int,
    r: int,
    *,
    is_causal: bool,
    offset: int,
    attn_fn: AttnFn,
    seq_axis_name: Optional[str],
    seq_axis_size: int,
    valid_len: Optional[jnp.ndarray] = None,
    gathered_counts: Optional[jnp.ndarray] = None,
    ring: bool = False,
    ring_allow_pallas: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One (segment_length, ratio) branch -> (out [B,L,H,D], lse [B,H,L]).

    ``gathered_counts``: the caller's hoisted ``[W, B]`` all-gather of
    per-rank valid counts (rank-local data, identical across branches —
    gathered once per ``dilated_attention`` call, not per branch).
    ``ring``: route a non-causal gathered branch through the
    ring-scheduled K/V exchange instead of the all-gather."""
    B, L, H, Dh = q.shape

    if offset > 0:
        # Incremental decoding (reference gathering:78-82 / scattering:113):
        # in the full forward, a query at global position t only attends keys
        # inside its own segment t//sl — so earlier key segments are
        # invisible and can be dropped. Slicing K/V to the query's segment
        # start and front-padding q by offset % sl realigns both to a common
        # within-segment coordinate system with Lq == Lk, after which the
        # standard equal-length path (incl. its causal mask and real-length
        # tail masks on the *sliced* cache) is exactly the decode math.
        assert seq_axis_name is None or seq_axis_size <= 1, (
            "offset decoding + sequence parallelism are not supported together"
        )
        s0 = (offset // sl) * sl
        if s0 > 0:
            k = k[:, s0:]
            v = v[:, s0:]
        q = jnp.pad(q, ((0, 0), (offset % sl, 0), (0, 0), (0, 0)))
    Lq = q.shape[1]

    gather_kv = (
        seq_axis_name is not None and seq_axis_size > 1 and sl > k.shape[1]
    )

    with jax.named_scope("pack"):
        g_q = min(sl, Lq)
        qp = _pad_to_multiple(q, g_q, axis=1)
        n_seg = qp.shape[1] // g_q
        qs = qp.reshape(B * n_seg, g_q, H, Dh)
        qs = dense_to_sparse(qs, r)

        g_k = min(sl, k.shape[1])
        kp = _pad_to_multiple(k, g_k, axis=1).reshape(-1, g_k, H, Dh)
        vp = _pad_to_multiple(v, g_k, axis=1).reshape(-1, g_k, H, Dh)
        ks = dense_to_sparse(kp, r)
        vs = dense_to_sparse(vp, r)

    kv_valid_len = None
    sp_causal_bias = None
    ring_result = None
    ring_counts = None
    if gather_kv:
        local_len = k.shape[1]
        # a segment longer than the whole sharded sequence is ONE segment
        # over all of it — the single-device path's g = min(sl, L), taken
        # here over the global length. Without it the flagship schedule
        # (185,363 / 1,048,576) neither divides into whole shards nor fits
        # the seq axis.
        sl = min(sl, seq_axis_size * local_len)
        use_ring = ring and not is_causal
        if ring and is_causal:
            # visible, once: silently taking the gather path would make
            # the flag look broken exactly where memory matters most
            _warn_once(
                "GIGAPATH_RING_ATTN requested on a CAUSAL gathered branch: "
                "the ring schedule has no rank-bias construction yet — "
                "using the all-gather path for this branch"
            )
        if valid_len is not None:
            if is_causal:
                raise NotImplementedError(
                    "causal + padding masks + sequence parallelism are not "
                    "supported together yet"
                )
            # Ragged gathered branch: ``valid_len`` is the LOCAL per-shard
            # suffix valid count (the module sums the sharded
            # key_padding_mask per shard). All-gather every rank's counts
            # and keep the ranks of my segment — mirroring
            # _gather_kv_seq_parallel's key selection — then count valid
            # sparse slots per (rank block, head phase): local slot j of
            # head phase p sits at local position p + r*j, valid iff
            # < that rank's count. GLOBAL suffix padding makes validity a
            # contiguous prefix of the concatenated key axis (every rank
            # before the cut is full), so a single per-(batch, head)
            # count is exact. A static int CANNOT express that: it is the
            # same partial count on EVERY rank, i.e. holes mid-axis that
            # a prefix count would silently mis-mask — refuse it.
            if isinstance(valid_len, (int, np.integer)):
                raise NotImplementedError(
                    "a static-int valid_len on a gathered sequence-parallel "
                    "branch would mask the same suffix on every shard — not "
                    "a contiguous prefix of the concatenated key axis; pass "
                    "the traced per-shard counts of a suffix-padded batch "
                    "(sum the sharded key_padding_mask) instead"
                )
            rps = sl // local_len
            m_loc = ks.shape[1]
            all_counts = gathered_counts  # hoisted: ONE gather per call
            if all_counts is None:  # direct/partial callers only
                vl_local = jnp.asarray(valid_len, jnp.int32).reshape(B)
                all_counts = jax.lax.all_gather(
                    vl_local, seq_axis_name, axis=0
                )  # [W, B]
            rank = jax.lax.axis_index(seq_axis_name)
            seg_counts = jax.lax.dynamic_slice_in_dim(
                all_counts, rank // rps * rps, rps, axis=0
            )  # [rps, B]
            heads_per_group = -(-H // r)
            phases = jnp.arange(H) // heads_per_group  # [H]
            per_rank = jnp.ceil(
                (seg_counts[:, :, None] - phases[None, None, :]) / r
            )
            per_rank = jnp.clip(per_rank, 0, m_loc).astype(jnp.int32)
            if use_ring:
                # keep the per-ORIGIN-rank table [rps, B, H]: each ring
                # step selects the resident chunk's row; the prefix sum
                # over concatenated keys never exists on the ring path
                ring_counts = per_rank
            else:
                kv_valid_len = per_rank.sum(axis=0)  # [B, H] == [B*n_seg, H]
            valid_len = None  # consumed
        if use_ring:
            assert sl % local_len == 0, (sl, local_len)
            rps = sl // local_len
            assert rps <= seq_axis_size, (
                f"gathered branch needs {rps} ranks but the seq axis has "
                f"{seq_axis_size}"
            )
            ring_result = _ring_attention(
                qs, ks, vs, ring_counts,
                seq_axis_name, seq_axis_size, rps, ring_allow_pallas,
            )
        else:
            ks = _gather_kv_seq_parallel(ks, sl, local_len, seq_axis_name)
            vs = _gather_kv_seq_parallel(vs, sl, local_len, seq_axis_name)
        if is_causal:
            # Causal sequence parallelism (reference gather_kv:64-68): ranks
            # of my segment *ahead* of me must be invisible, earlier ranks
            # fully visible, my own rank causally visible. Key slot j of rank
            # block w' and query slot i share a head phase p, so global order
            # reduces to block-and-slot order: key (w', j) <= query (w, i)
            # iff j_cat <= w_rel*m + i in the concatenated key axis. The
            # reference's literal dormant code instead drops the current
            # rank's own keys and zero-stubs rank 0 (`x[:1] * 0`), which
            # breaks self-attention; this implements the evident intent (see
            # PARITY.md). The rank index is traced, so the mask rides as an
            # additive bias instead of the static causal flag.
            rps = sl // local_len
            m_loc = ks.shape[1] // rps
            w_rel = jax.lax.axis_index(seq_axis_name) % rps
            qi = jnp.arange(qs.shape[1])[:, None]
            kj = jnp.arange(ks.shape[1])[None, :]
            sp_causal_bias = jnp.where(
                kj <= qi + w_rel * m_loc, 0.0, NEG_INF
            )[None, None]  # [1, 1, Lq_sparse, Lk_cat]
            is_causal = False  # superseded by the bias
    else:
        static_len = k.shape[1]
        if isinstance(valid_len, int):
            static_len = min(valid_len, static_len)
            valid_len = None  # folded into the static tail masks below
        kv_valid_len = _kv_valid_lengths(
            B, kp.shape[0] // B, g_k, r, ks.shape[1], H, static_len
        )
        if valid_len is not None:
            # dynamic per-batch suffix padding: same segment/dilation count
            # formula as _kv_valid_lengths, with the traced valid length in
            # place of the static real length; combined by min
            n_seg_k = kp.shape[0] // B
            m = ks.shape[1]
            heads_per_group = -(-H // r)
            phases = jnp.arange(H) // heads_per_group  # [H]
            seg = jnp.arange(n_seg_k)  # [n_seg]
            counts = jnp.ceil(
                (
                    valid_len[:, None, None]
                    - seg[None, :, None] * g_k
                    - phases[None, None, :]
                )
                / r
            )
            counts = jnp.clip(counts, 0, m).astype(jnp.int32).reshape(B * n_seg_k, H)
            kv_valid_len = (
                counts
                if kv_valid_len is None
                else jnp.minimum(counts, jnp.asarray(kv_valid_len, jnp.int32))
            )

    if ring_result is not None:
        out_s, lse_s = ring_result
    elif sp_causal_bias is not None:
        out_s, lse_s = attn_fn(
            qs, ks, vs, is_causal=False, kv_valid_len=None, bias=sp_causal_bias
        )
    else:
        out_s, lse_s = attn_fn(
            qs, ks, vs, is_causal=is_causal, kv_valid_len=kv_valid_len
        )

    with jax.named_scope("unpack"):
        out_d, lse_d = sparse_to_dense(out_s, lse_s, r, g_q)
        out = out_d.reshape(B, n_seg * g_q, H, Dh)
        lse = lse_d.reshape(B, n_seg, H, g_q).transpose(0, 2, 1, 3).reshape(B, H, -1)
        start = offset % sl if offset > 0 else 0
        return out[:, start : start + L], lse[..., start : start + L]


class DilatedAttention(MultiheadAttention):
    """LongNet attention module: MHA projections around dilated attention.

    Parity with reference ``DilatedAttention(MultiheadAttention)``
    (``dilated_attention.py:14``): same q/k/v/out projections, sub-LN, and
    branch schedule from the config. ``seq_axis_name`` activates sequence
    parallelism when the module runs inside ``shard_map``.
    """

    segment_length: Sequence[int] = ()
    dilated_ratio: Sequence[int] = ()
    seq_parallel: bool = False
    seq_axis_name: Optional[str] = None
    seq_axis_size: int = 1
    attn_fn: Optional[AttnFn] = None

    def _cached_attend_inputs(self, k, v, cur, Lq, attn_mask, is_causal):
        """Positional (offset-based) incremental decode.

        The segment/dilation structure depends on absolute positions, so the
        cache is consumed as ``offset = cur`` plus the live prefix of the
        buffer — not as a dense mask over the full static buffer (the base
        class mechanism), which dilated attention cannot honor. The cache
        index must be concrete (eager generation loop, as in the reference's
        fairseq-style decoding); a traced index raises with guidance.
        """
        try:
            off = int(cur)
        except jax.errors.ConcretizationTypeError as e:
            raise NotImplementedError(
                "DilatedAttention incremental decode requires a concrete "
                "cache index (run the generation loop eagerly, outside jit): "
                "segment boundaries are position-dependent static shapes"
            ) from e
        k = k[:, : off + Lq]
        v = v[:, : off + Lq]
        return k, v, attn_mask, is_causal, off

    def _attend(
        self,
        q,
        k,
        v,
        *,
        key_padding_mask=None,
        attn_mask=None,
        rel_pos=None,
        is_causal: bool = False,
        deterministic: bool = True,
        offset: int = 0,
    ):
        assert rel_pos is None, "dilated attention does not support rel_pos bias"
        assert attn_mask is None, "dilated attention does not support attn_mask"
        # key_padding_mask (True = pad) is consumed as a *suffix* valid
        # length: batches are collated with trailing padding (data/collate.py),
        # so per-row valid counts capture the mask exactly. (The reference's
        # live path drops the mask entirely, SURVEY §2.7; its dead custom_*
        # files sketch the same per-branch masking implemented here.)
        # A concrete (numpy) mask with one shared count — the slide encoder's
        # internal alignment padding — stays a static int, keeping Pallas.
        valid_len = None
        if key_padding_mask is not None:
            if isinstance(key_padding_mask, np.ndarray):
                counts = (~key_padding_mask).sum(axis=-1)
                assert (counts == counts[0]).all(), (
                    "concrete ragged masks unsupported; pass a traced mask"
                )
                valid_len = int(counts[0])
            else:
                valid_len = (~key_padding_mask).sum(axis=-1).astype(jnp.int32)
        rng = None
        if self.dropout > 0.0 and not deterministic:
            rng = self.make_rng("dropout")
        out = dilated_attention(
            q,
            k,
            v,
            tuple(self.segment_length),
            tuple(self.dilated_ratio),
            is_causal=is_causal,
            offset=offset,
            attn_fn=self.attn_fn,
            seq_axis_name=self.seq_axis_name if self.seq_parallel else None,
            seq_axis_size=self.seq_axis_size if self.seq_parallel else 1,
            dropout_rate=0.0 if deterministic else self.dropout,
            dropout_rng=rng,
            valid_len=valid_len,
        )
        return out.reshape(out.shape[0], out.shape[1], self.embed_dim)
