"""Pallas flash kernel vs the jnp reference, in interpreter mode on CPU."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapath_tpu.ops.attention import attention_with_lse
from gigapath_tpu.ops.pallas_flash import pallas_flash_attention

flash = functools.partial(pallas_flash_attention, interpret=True)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(1, 128, 2, 16), (2, 300, 3, 48)])
def test_forward_matches_reference(rng, causal, shape):
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(3))
    out, lse = flash(q, k, v, is_causal=causal)
    ref_out, ref_lse = attention_with_lse(q, k, v, is_causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=1e-4)


def test_forward_bf16(rng):
    shape = (1, 256, 2, 32)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.bfloat16) for _ in range(3))
    out, lse = flash(q, k, v)
    ref_out, ref_lse = attention_with_lse(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref_out, np.float32), atol=3e-2
    )
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=3e-2, rtol=1e-2)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(rng, causal):
    shape = (1, 192, 2, 16)
    q, k, v = (jnp.asarray(rng.normal(size=shape), jnp.float32) for _ in range(3))

    def loss_flash(q, k, v):
        out, _ = flash(q, k, v, is_causal=causal)
        return (out * out).sum()

    def loss_ref(q, k, v):
        out, _ = attention_with_lse(q, k, v, is_causal=causal)
        return (out * out).sum()

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )


@pytest.mark.parametrize("lens", [[7, 64, 0, 33], [64, 64, 64, 64], [1, 2, 3, 4]])
def test_kv_len_ragged_masking(rng, lens):
    """Per-(batch,head) valid-key counts: forward, lse, and grads must match
    the jnp reference with the same kv_valid_len (incl. a zero-length row)."""
    B, L, H, D = 2, 64, 2, 16
    kv = np.asarray(lens, np.int32).reshape(B, H)
    q, k, v = (jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32) for _ in range(3))
    out_p, lse_p = flash(q, k, v, kv_len=kv)
    out_j, lse_j = attention_with_lse(q, k, v, kv_valid_len=kv)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_j), atol=2e-5, rtol=1e-4)
    # lse is implementation-defined (~NEG_INF scale) on zero-valid rows;
    # both paths give such rows ~zero weight in the dilated branch fusion
    nonempty = (kv > 0)[:, :, None] * np.ones((B, H, L), bool)
    np.testing.assert_allclose(
        np.asarray(lse_p)[nonempty], np.asarray(lse_j)[nonempty], atol=2e-4, rtol=1e-4
    )

    def loss_p(q, k, v):
        o, _ = flash(q, k, v, kv_len=kv)
        return (o * o).sum()

    def loss_j(q, k, v):
        o, _ = attention_with_lse(q, k, v, kv_valid_len=kv)
        return (o * o).sum()

    g1 = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_j, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g1, g2, "qkv"):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3, err_msg=f"d{name}"
        )


def test_kv_len_masks_large_real_keys(rng):
    """Masked key slots holding LARGE real activations (alignment padding
    becomes nonzero after residual layers) must not perturb outputs, lse, or
    gradients — a post-softmax zero-multiply would let them dominate the
    running max (underflowing valid rows) and produce inf*0 NaNs in the
    backward. Regression for the column-bias masking."""
    B, L, H, D = 1, 64, 2, 16
    n_valid = 40
    kv = np.full((B, H), n_valid, np.int32)
    q = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.float32)
    # masked tail keys are huge -> logits ~ +-40*|q| >> valid logits
    k = k.at[:, n_valid:].set(40.0)
    v = v.at[:, n_valid:].set(40.0)

    out_p, lse_p = flash(q, k, v, kv_len=kv)
    ref, lse_ref = attention_with_lse(
        q, k[:, :n_valid], v[:, :n_valid]
    )
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(ref), atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(lse_p), np.asarray(lse_ref), atol=2e-4, rtol=1e-4)

    def loss_p(q, k, v):
        o, _ = flash(q, k, v, kv_len=kv)
        return (o * o).sum()

    grads = jax.grad(loss_p, argnums=(0, 1, 2))(q, k, v)
    for g, name in zip(grads, "qkv"):
        assert np.isfinite(np.asarray(g)).all(), f"d{name} has NaN/inf"
    # masked key/value slots receive zero gradient
    np.testing.assert_allclose(np.asarray(grads[1][:, n_valid:]), 0.0, atol=1e-6)
    np.testing.assert_allclose(np.asarray(grads[2][:, n_valid:]), 0.0, atol=1e-6)


def test_unaligned_lengths(rng):
    """L not a multiple of the block size: padded keys must be masked."""
    q, k, v = (jnp.asarray(rng.normal(size=(1, 333, 2, 48)), jnp.float32) for _ in range(3))
    out, lse = flash(q, k, v)
    ref_out, ref_lse = attention_with_lse(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref_out), atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=2e-5, rtol=1e-4)


def test_bwd_blocks_fit_budget():
    """The backward block pair must fit the backward's scoped-vmem budget.

    Regression for the round-3 driver crash: m=1281 (flagship r=8 branch at
    N=10241) picks a 1408 forward single block, and reusing it squared in
    the backward overflowed scoped vmem (20.12 MB vs the 16 MB limit)."""
    from gigapath_tpu.ops.dilated_attention import _bhld_geom
    from gigapath_tpu.ops.pallas_flash import _BWD_LOGITS_BUDGET, bwd_blocks

    # the exact crash geometry: flagship r=8 branch at N=10241
    *_rest, m, fwd_block = _bhld_geom(10241, 185363, 8)
    assert (m, fwd_block) == (1281, 1408)
    bq, bk = bwd_blocks(fwd_block)
    assert bq == 1408, "q side should keep the forward block (stays unpadded)"
    assert bq * bk <= _BWD_LOGITS_BUDGET
    # every forward block the adaptive dispatcher can emit stays in budget
    for fb in (128, 640, 768, 1024, 1280, 1408):
        bq, bk = bwd_blocks(fb)
        assert bq == fb and bk % 128 == 0
        assert bq * bk <= _BWD_LOGITS_BUDGET, (fb, bq, bk)


def test_bwd_impl_asymmetric_blocks_match(rng):
    """dq/dk/dv must be invariant to the (block_q, block_k) choice."""
    from gigapath_tpu.ops import pallas_flash as pf

    B, H, S, M, D = 1, 2, 2, 320, 16
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, H, S, M, D)), jnp.float32)
        for _ in range(3)
    )
    do = jnp.asarray(rng.normal(size=(B, H, S, M, D)), jnp.float32)
    out, lse = pf._fwd_impl(q, k, v, None, False, D ** -0.5, 128, 128, True)
    delta = jnp.sum(do * out, axis=-1)

    ref = pf._bwd_impl(q, k, v, lse, delta, do, None, False, D ** -0.5, 128, 128, True)
    for bq, bk in ((256, 128), (128, 256), (320, 128)):
        got = pf._bwd_impl(
            q, k, v, lse, delta, do, None, False, D ** -0.5, bq, bk, True
        )
        for a, b, name in zip(got, ref, ("dq", "dk", "dv")):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4,
                err_msg=f"{name} differs at blocks ({bq}, {bk})",
            )


def test_flat_bwd_resegment_fallback_matches(rng, monkeypatch):
    """The oversized-g flat backward (re-segment + generic kernels) must
    match the single-block flat backward on the valid region."""
    from gigapath_tpu.ops import pallas_flash as pf

    B, H, L, D, g, rl = 1, 2, 600, 16, 256, 580
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.float32)
        for _ in range(3)
    )

    def loss(q, k, v):
        out, _ = pf.flat_segment_flash(
            q, k, v, segment_len=g, real_len=rl, interpret=True
        )
        return (out[:, :, :rl] ** 2).sum()

    g_normal = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # smallest legal budget that still forces the fallback at this g
    monkeypatch.setattr(pf, "_BWD_LOGITS_BUDGET", g * 128)
    g_fallback = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_fallback, g_normal, ("dq", "dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4,
            err_msg=f"{name} differs between fallback and flat backward",
        )


def test_flat_bwd_fallback_masks_invalid_row_cotangents(rng, monkeypatch):
    """A cotangent touching rows beyond real_len (out is garbage there by
    contract) must contribute nothing to dk/dv in the fallback — matching
    the flat=True kernels' qrow zeroing, so gradient semantics don't flip
    across the budget threshold."""
    from gigapath_tpu.ops import pallas_flash as pf

    B, H, L, D, g, rl = 1, 2, 600, 16, 256, 580
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, H, L, D)), jnp.float32)
        for _ in range(3)
    )

    def loss(q, k, v):
        out, _ = pf.flat_segment_flash(
            q, k, v, segment_len=g, real_len=rl, interpret=True
        )
        return (out ** 2).sum()  # deliberately touches rows in [rl, L)

    g_normal = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # smallest legal budget that still forces the fallback at this g
    monkeypatch.setattr(pf, "_BWD_LOGITS_BUDGET", g * 128)
    g_fallback = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    # dk/dv must agree everywhere; dq only on the valid region (invalid
    # rows' dq is garbage-on-garbage in the flat path, zero in the fallback)
    for a, b, name in zip(g_fallback[1:], g_normal[1:], ("dk", "dv")):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-4,
            err_msg=f"{name} differs with invalid-row cotangents",
        )
    np.testing.assert_allclose(
        np.asarray(g_fallback[0][:, :, :rl]), np.asarray(g_normal[0][:, :, :rl]),
        atol=1e-5, rtol=1e-4, err_msg="dq differs on the valid region",
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2), (8, 2), (4, 1)])
def test_grouped_kv_heads_and_scale_match_reference(rng, causal, heads, kv_heads):
    """Query head h reads KV head h // (heads / kv_heads) through the K/V
    index map; the scale is an argument (granite's 1/128 is not D ** -0.5).
    L = 300 with blocks of 128: three blocks a row, so a causal run skips the
    blocks above the diagonal and clamps their index."""
    L, D, scale = 300, 16, 0.11
    q = jnp.asarray(rng.normal(size=(2, L, heads, D)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(2, L, kv_heads, D)), jnp.float32) for _ in range(2))
    from gigapath_tpu.ops import pallas_flash as pf

    q5, k5, v5 = (x.transpose(0, 2, 1, 3)[:, :, None] for x in (q, k, v))
    out, lse = pf._fwd_impl(q5, k5, v5, None, causal, scale, 128, 128, True)
    ref_out, ref_lse = attention_with_lse(q, k, v, is_causal=causal, scale=scale)
    np.testing.assert_allclose(out[:, :, 0].transpose(0, 2, 1, 3), ref_out, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(lse[:, :, 0], ref_lse, atol=2e-5, rtol=1e-4)
    out2, _ = flash(q, k, v, is_causal=causal, scale=scale)  # the public wrapper, default blocks
    np.testing.assert_allclose(out2, ref_out, atol=2e-5, rtol=1e-4)


def test_grouped_kv_gradients_sum_over_the_group(rng):
    q = jnp.asarray(rng.normal(size=(1, 192, 4, 16)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(1, 192, 2, 16)), jnp.float32) for _ in range(2))

    def loss(op):
        return lambda q, k, v: (op(q, k, v, is_causal=True, scale=0.2)[0] ** 2).sum()

    got = jax.grad(loss(flash), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(attention_with_lse), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-4, rtol=1e-3)


def test_equal_heads_default_scale_lowers_to_the_parents_text():
    """The slide encoder's and the ViT's side of the shared kernel: with H_kv
    = H, no ``scale`` and no causal mask the wrapper lowers to the text it
    lowered to before grouped KV heads, ``scale`` and diagonal skipping were
    added (sha256 taken from commit 7080963's tree, same JAX)."""
    import hashlib

    q = jax.ShapeDtypeStruct((1, 256, 4, 64), jnp.bfloat16)
    text = jax.jit(lambda q, k, v: flash(q, k, v)).lower(q, q, q).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "03973aa7e43ea55861e1e390559dab264c31b7d950e17740d78379dac376b52c")


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("heads,kv_heads,d,dv", [(4, 4, 24, 16), (4, 2, 16, 32), (2, 2, 192, 128)])
def test_value_width_of_its_own_matches_reference(rng, causal, heads, kv_heads, d, dv):
    """Latent attention's core: keys of ``d`` beside values of ``dv`` (192 /
    128 at A.X-K1's widths). The value block, the output and the accumulator
    take ``dv``; nothing is padded to ``d``. L = 300 with blocks of 128, so a
    causal run skips and clamps as it does at equal widths."""
    L, scale = 300, 0.13
    q = jnp.asarray(rng.normal(size=(2, L, heads, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(2, L, kv_heads, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(2, L, kv_heads, dv)), jnp.float32)
    from gigapath_tpu.ops import pallas_flash as pf

    q5, k5, v5 = (x.transpose(0, 2, 1, 3)[:, :, None] for x in (q, k, v))
    out, lse = pf._fwd_impl(q5, k5, v5, None, causal, scale, 128, 128, True)
    ref_out, ref_lse = attention_with_lse(q, k, v, is_causal=causal, scale=scale)  # the jnp tier
    assert out.shape == (2, heads, 1, L, dv) and ref_out.shape == (2, L, heads, dv)
    np.testing.assert_allclose(out[:, :, 0].transpose(0, 2, 1, 3), ref_out, atol=2e-5, rtol=1e-4)
    np.testing.assert_allclose(lse[:, :, 0], ref_lse, atol=2e-5, rtol=1e-4)
    out2, _ = flash(q, k, v, is_causal=causal, scale=scale)  # the public wrapper, default blocks
    np.testing.assert_allclose(out2, ref_out, atol=2e-5, rtol=1e-4)
    # by hand, one head and one row: softmax over the allowed keys, then the values
    b, h, t = 1, heads - 1, L - 7
    kh = h // (heads // kv_heads)
    keys = slice(0, t + 1) if causal else slice(0, L)
    s = np.asarray(q[b, t, h]) @ np.asarray(k[b, keys, kh]).T * scale
    w = np.exp(s - s.max())
    np.testing.assert_allclose(out2[b, t, h], (w / w.sum()) @ np.asarray(v[b, keys, kh]),
                               atol=2e-5, rtol=1e-4)


def test_default_scale_is_the_keys_width_and_both_tiers_take_the_values(rng):
    from gigapath_tpu.ops.flash_attention import flash_attention

    q, k = (jnp.asarray(rng.normal(size=(1, 128, 2, 24)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 8)), jnp.float32)
    want, _ = attention_with_lse(q, k, v, is_causal=True, scale=24 ** -0.5)
    got, _ = flash(q, k, v, is_causal=True)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=1e-4)
    jnp_tier, lse = flash_attention(q, k, v, is_causal=True, use_pallas=False)
    assert jnp_tier.shape == (1, 128, 2, 8) and lse.shape == (1, 2, 128)
    np.testing.assert_allclose(jnp_tier, want, atol=2e-5, rtol=1e-4)


def test_unequal_widths_are_forward_only_on_the_kernel_tier(rng):
    q, k = (jnp.asarray(rng.normal(size=(1, 128, 2, 24)), jnp.float32) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(1, 128, 2, 8)), jnp.float32)
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(lambda q: flash(q, k, v)[0].sum())(q)
    g = jax.grad(lambda q: attention_with_lse(q, k, v)[0].sum())(q)  # the jnp tier differentiates
    assert g.shape == q.shape and np.isfinite(g).all()


def _differing(a, b):
    return int((np.asarray(a, np.float32) != np.asarray(b, np.float32)).sum())


# (B, H, H_kv, L, D, Dv, block_q, block_k, kv_lens a (batch, head), dtype)
_BODY_CASES = {
    # Granite's side at the shape whose serial text was pinned until PR 37
    "grouped_equal_widths_256": (1, 4, 2, 256, 64, 64, 128, 128, None, jnp.bfloat16),
    "grouped_equal_widths_three_blocks": (2, 8, 2, 300, 32, 32, 128, 128, None, jnp.float32),
    "keys_192_values_128": (1, 2, 2, 300, 192, 128, 128, 128, None, jnp.bfloat16),
    "unaligned_length": (1, 2, 2, 333, 48, 48, 128, 128, None, jnp.float32),
    "ragged_kv_lens": (2, 2, 1, 256, 32, 32, 128, 128, (7, 256, 0, 130), jnp.bfloat16),
    "single_key_block": (1, 2, 2, 100, 32, 32, 128, 128, None, jnp.bfloat16),
    # blocks of 256: a chain of 128 rows on the diagonal block takes 128 of its 256 keys
    "diagonal_block_cut_a_chain": (1, 2, 1, 600, 32, 32, 256, 256, None, jnp.bfloat16),
    "diagonal_block_cut_a_chain_ragged": (1, 2, 2, 600, 48, 32, 256, 256, (600, 300),
                                          jnp.float32),
    "block_q_twice_block_k": (1, 2, 1, 384, 32, 32, 256, 128, None, jnp.bfloat16),
    "block_k_twice_block_q": (1, 2, 1, 384, 32, 32, 128, 256, None, jnp.bfloat16),
}


@pytest.mark.parametrize("case", _BODY_CASES)
@pytest.mark.parametrize("chains", ["planned", 2, 4])
def test_planned_causal_body_is_the_serial_body_to_the_bit(rng, case, chains):
    """The overlapped body cuts rows, steps through the visited blocks alone and
    leaves out only compares that were identities and keys whose ``p`` was an
    exact zero: ``out`` and ``lse`` equal the serial body's in every element,
    at the planner's chains and at two and four a block. The first case takes
    the place of ``test_equal_widths_grouped_causal_lowers_to_the_parents_
    text`` (the serial causal text's sha256): that call now takes the
    overlapped body by design, and is held to the serial one here."""
    from gigapath_tpu.ops import pallas_flash as pf

    B, H, Hkv, L, D, Dv, bq, bk, kv_lens, dtype = _BODY_CASES[case]
    if chains == 4 and dtype == jnp.float32:
        # a float32 product of 32 rows takes another routine of the CPU's (one ulp
        # in ``out``; bfloat16 operands multiply exactly): chains of 64 rows there
        bq, bk = 2 * bq, 2 * bk
    q = jnp.asarray(rng.normal(size=(B, H, 1, L, D)), dtype)
    k = jnp.asarray(rng.normal(size=(B, Hkv, 1, L, D)), dtype)
    v = jnp.asarray(rng.normal(size=(B, Hkv, 1, L, Dv)), dtype)
    block = min(bq, pf.round_up(L, pf.LANES))
    plan = pf.plan_fwd_body("causal", block, pairs=9)  # at most three blocks a side here
    assert plan == pf.FwdPlan("overlap", max(block // 4, 128))
    body = None if chains == "planned" else plan._replace(rows=block // chains)
    want = pf._fwd_impl(q, k, v, kv_lens, True, 0.11, bq, bk, True, body="serial")
    got = pf._fwd_impl(q, k, v, kv_lens, True, 0.11, bq, bk, True, body=body)
    assert (_differing(got[0], want[0]), _differing(got[1], want[1])) == (0, 0)
    assert np.isfinite(np.asarray(want[0], np.float32)).all()


def test_overlapped_body_without_a_mask_is_the_serial_body_to_the_bit(rng):
    """The mask is a parameter of the one body: with none (every pair of
    blocks visited, ragged counts from the table) it is still the serial
    body's arithmetic, though no caller is planned onto it."""
    from gigapath_tpu.ops import pallas_flash as pf

    q, k, v = (jnp.asarray(rng.normal(size=(2, 2, 1, 300, 32)), jnp.bfloat16) for _ in range(3))
    kv_lens = (300, 200, 0, 129)
    want = pf._fwd_impl(q, k, v, kv_lens, False, 0.11, 128, 128, True)
    got = pf._fwd_impl(q, k, v, kv_lens, False, 0.11, 128, 128, True,
                       body=pf.FwdPlan("overlap", 64))
    assert (_differing(got[0], want[0]), _differing(got[1], want[1])) == (0, 0)


def test_the_body_a_call_took_is_its_kernels_name():
    """``flash_fwd_overlap`` for a causal call, ``flash_fwd`` for the rest and
    for ``body="serial"``; no mask keeps the serial body (and the text
    ``test_equal_heads_default_scale_lowers_to_the_parents_text`` pins)."""
    from gigapath_tpu.ops import pallas_flash as pf

    assert pf.plan_fwd_body(None, 1024, 256) == pf.FwdPlan("serial", 1024)
    assert pf.plan_fwd_body("causal", 1024, 256) == pf.FwdPlan("overlap", 256)
    assert pf.plan_fwd_body("selection", 1024, 256) == pf.FwdPlan("overlap", 256)
    assert pf.plan_fwd_body("causal", 128, 1) == pf.FwdPlan("overlap", 128)
    # a million tokens: the tables of steps would not fit beside the kernel's scalars
    assert pf.plan_fwd_body("causal", 1024, 1024 * 1024) == pf.FwdPlan("serial", 1024)
    x = jax.ShapeDtypeStruct((1, 2, 1, 256, 64), jnp.bfloat16)

    def names(causal, body=None):
        text = jax.jit(lambda q, k, v: pf._fwd_impl(q, k, v, None, causal, 0.1, 128, 128, True,
                                                    body=body)).lower(x, x, x).as_text(debug_info=True)
        return {"overlap": "kernel_fwd/flash_fwd_overlap/" in text,
                "serial": "kernel_fwd/flash_fwd/" in text}

    assert names(True) == {"overlap": True, "serial": False}
    assert names(True, "serial") == {"overlap": False, "serial": True}
    assert names(False) == {"overlap": False, "serial": True}


@pytest.mark.parametrize("L,block", [(300, 128), (600, 256)])
def test_a_lower_triangle_selection_is_the_causal_flash_forward_to_the_bit(rng, L, block):
    """One body behind both calls: ``sparse_attn`` with every earlier key
    selected gives ``flash_fwd``'s causal ``out`` (serial body) in every
    element, at keys of 24 beside values of 16 and three blocks a row (of
    128, and of 256, where the diagonal block is cut a chain)."""
    from gigapath_tpu.ops import pallas_flash as pf
    from gigapath_tpu.ops.pallas_sparse import sparse_attn_fwd

    B, H, D, Dv = 2, 2, 24, 16
    q, k = (jnp.asarray(rng.normal(size=(B, L, H, D)), jnp.bfloat16) for _ in range(2))
    v = jnp.asarray(rng.normal(size=(B, L, H, Dv)), jnp.bfloat16)
    triangle = jnp.asarray(np.tril(np.ones((B, L, L), np.int8)))
    got = sparse_attn_fwd(q, k, v, triangle, scale=0.2, block_q=block, block_k=block,
                          interpret=True)
    q5, k5, v5 = (x.transpose(0, 2, 1, 3)[:, :, None] for x in (q, k, v))
    want, _ = pf._fwd_impl(q5, k5, v5, None, True, 0.2, block, block, True, body="serial")
    assert _differing(got, want[:, :, 0].transpose(0, 2, 1, 3)) == 0
