"""Dilated attention vs independent numpy oracle + vanilla equivalence.

The reference's own statement of correctness is its `LongNet_Vanilla_*`
configs (dilated ratio [1], segment 10^7 => must equal full attention); we
test that plus a general multi-branch oracle the reference never had.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gigapath_tpu.ops.attention import attention_with_lse
from gigapath_tpu.ops.dilated_attention import (
    DilatedAttention,
    dense_to_sparse,
    dilated_attention,
    sparse_to_dense,
)


def _np_softmax_attn(q, k, v):
    D = q.shape[-1]
    logits = q @ k.T / np.sqrt(D)
    m = logits.max(-1, keepdims=True)
    e = np.exp(logits - m)
    p = e / e.sum(-1, keepdims=True)
    lse = np.log(e.sum(-1)) + m[:, 0]
    return p @ v, lse


def _np_dilated_oracle(q, k, v, branches):
    """Per-position/per-head oracle: each branch restricts attention to the
    dilated subset of its segment; branches fuse by softmax over lse."""
    B, N, H, D = q.shape
    outs = np.zeros((len(branches), B, N, H, D))
    lses = np.full((len(branches), B, N, H), -1e8)
    for bi, (sl, r) in enumerate(branches):
        g = min(sl, N)
        heads_per_group = -(-H // r)
        for b in range(B):
            for s0 in range(0, N, g):
                for h in range(H):
                    phase = h // heads_per_group
                    pos = np.arange(s0 + phase, min(s0 + g, N), r)
                    if len(pos) == 0:
                        continue
                    o, lse = _np_softmax_attn(q[b, pos, h], k[b, pos, h], v[b, pos, h])
                    outs[bi, b, pos, h] = o
                    lses[bi, b, pos, h] = lse
    w = np.exp(lses - lses.max(0))
    w = w / w.sum(0)
    return (outs * w[..., None]).sum(0)


def test_dense_sparse_roundtrip(rng):
    x = jnp.asarray(rng.normal(size=(3, 8, 4, 5)), jnp.float32)
    s = dense_to_sparse(x, 2)
    assert s.shape == (3, 4, 4, 5)
    lse = jnp.zeros((3, 4, 4))
    d, lse_d = sparse_to_dense(s, lse, 2, 8)
    # every selected position must round-trip exactly
    s2 = dense_to_sparse(d, 2)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s))
    # uncovered positions have NEG_INF lse
    assert (np.asarray(lse_d) == -1e8).sum() == 3 * 4 * 4


@pytest.mark.parametrize("sl", [64, 1_000_000])
def test_single_branch_ratio1_equals_vanilla(rng, sl):
    q, k, v = (jnp.asarray(rng.normal(size=(2, 32, 4, 8)), jnp.float32) for _ in range(3))
    out = dilated_attention(q, k, v, [sl], [1])
    ref, _ = attention_with_lse(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_segmented_ratio1_is_block_diagonal(rng):
    q, k, v = (jnp.asarray(rng.normal(size=(1, 32, 2, 8)), jnp.float32) for _ in range(3))
    out = dilated_attention(q, k, v, [8], [1])
    for s in range(0, 32, 8):
        ref, _ = attention_with_lse(q[:, s : s + 8], k[:, s : s + 8], v[:, s : s + 8])
        np.testing.assert_allclose(np.asarray(out[:, s : s + 8]), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize(
    "branches,N,H",
    [
        ([(8, 2)], 16, 4),
        ([(4, 1), (8, 2)], 16, 4),
        ([(4, 1), (8, 2), (16, 4)], 32, 8),
        ([(8, 4)], 16, 2),  # more phases than heads-per-group edge
        ([(6, 2)], 13, 4),  # non-power-of-two, padding paths
    ],
)
def test_multibranch_matches_oracle(rng, branches, N, H):
    q, k, v = (rng.normal(size=(2, N, H, 4)).astype(np.float32) for _ in range(3))
    out = dilated_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        [sl for sl, _ in branches], [r for _, r in branches],
    )
    ref = _np_dilated_oracle(q, k, v, branches)
    np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=1e-4)


def test_causal_single_branch(rng):
    q, k, v = (jnp.asarray(rng.normal(size=(1, 16, 2, 4)), jnp.float32) for _ in range(3))
    out = dilated_attention(q, k, v, [16], [1], is_causal=True)
    ref, _ = attention_with_lse(q, k, v, is_causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_module_gigapath_schedule(rng):
    """Flagship 5-branch schedule on a short sequence (all sl >= N)."""
    mod = DilatedAttention(
        embed_dim=32,
        num_heads=4,
        segment_length=(1024, 2048, 4096, 8192, 16384),
        dilated_ratio=(1, 2, 4, 8, 16),
    )
    x = jnp.asarray(rng.normal(size=(1, 100, 32)), jnp.float32)
    params = mod.init(jax.random.PRNGKey(0), x, x, x)
    out = mod.apply(params, x, x, x)
    assert out.shape == (1, 100, 32)
    assert np.isfinite(np.asarray(out)).all()


def test_gradients_flow(rng):
    q, k, v = (jnp.asarray(rng.normal(size=(1, 16, 2, 4)), jnp.float32) for _ in range(3))

    def loss(q):
        return dilated_attention(q, k, v, [4, 8], [1, 2]).sum()

    g = jax.grad(loss)(q)
    assert np.isfinite(np.asarray(g)).all()
    assert np.abs(np.asarray(g)).sum() > 0


def test_seq_parallel_matches_single_device(rng):
    """shard_map over a 4-way seq axis == single-device dilated attention."""
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("seq",))
    N, H, D = 32, 4, 8
    q, k, v = (jnp.asarray(rng.normal(size=(1, N, H, D)), jnp.float32) for _ in range(3))
    sls, drs = [4, 16, 32], [1, 2, 4]  # 16 and 32 exceed the 8-token local shard

    ref = dilated_attention(q, k, v, sls, drs)

    fn = shard_map(
        lambda q, k, v: dilated_attention(
            q, k, v, sls, drs, seq_axis_name="seq", seq_axis_size=4
        ),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
    )
    out = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_seq_parallel_causal_matches_single_device(rng):
    """Causal shard_map SP == single-device causal dilated attention.

    Covers reference ``gather_kv``'s causal branch (dilated_attention.py:64-68)
    with the corrected semantics (own-rank keys kept, causal across rank
    blocks) — see PARITY.md for the deviation note.
    """
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("seq",))
    N, H, D = 32, 4, 8
    q, k, v = (jnp.asarray(rng.normal(size=(1, N, H, D)), jnp.float32) for _ in range(3))
    sls, drs = [4, 16, 32], [1, 2, 4]  # 16 and 32 exceed the 8-token local shard

    ref = dilated_attention(q, k, v, sls, drs, is_causal=True)

    fn = shard_map(
        lambda q, k, v: dilated_attention(
            q, k, v, sls, drs, is_causal=True, seq_axis_name="seq", seq_axis_size=4
        ),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
    )
    out = fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


class TestOffsetDecode:
    """Incremental decoding (offset > 0, Lq != Lk) == rows of the full
    causal forward — the contract of reference ``gathering``/``scattering``
    with ``offset`` (dilated_attention.py:78-82,113)."""

    SLS, DRS = [4, 16], [1, 2]

    def test_stepwise_matches_full(self, rng):
        N, H, D = 24, 4, 8  # N > 16: caches longer than the largest segment
        q, k, v = (jnp.asarray(rng.normal(size=(2, N, H, D)), jnp.float32) for _ in range(3))
        full = dilated_attention(q, k, v, self.SLS, self.DRS, is_causal=True)
        for t in [0, 1, 3, 4, 7, 15, 16, 17, 23]:
            step = dilated_attention(
                q[:, t : t + 1], k[:, : t + 1], v[:, : t + 1],
                self.SLS, self.DRS, is_causal=True, offset=t,
            )
            np.testing.assert_allclose(
                np.asarray(step[:, 0]), np.asarray(full[:, t]),
                atol=2e-5, rtol=1e-4, err_msg=f"step {t}",
            )

    def test_chunked_matches_full(self, rng):
        """Multi-token chunks, including chunks crossing segment boundaries."""
        N, H, D = 24, 4, 8
        q, k, v = (jnp.asarray(rng.normal(size=(1, N, H, D)), jnp.float32) for _ in range(3))
        full = dilated_attention(q, k, v, self.SLS, self.DRS, is_causal=True)
        for t0, t1 in [(0, 3), (3, 9), (9, 24)]:  # (3,9) crosses the sl=4 boundary
            chunk = dilated_attention(
                q[:, t0:t1], k[:, :t1], v[:, :t1],
                self.SLS, self.DRS, is_causal=True, offset=t0,
            )
            np.testing.assert_allclose(
                np.asarray(chunk), np.asarray(full[:, t0:t1]),
                atol=2e-5, rtol=1e-4, err_msg=f"chunk [{t0}, {t1})",
            )

    def test_bad_cache_length_raises(self, rng):
        q, k, v = (jnp.asarray(rng.normal(size=(1, 8, 2, 4)), jnp.float32) for _ in range(3))
        with pytest.raises(ValueError, match="offset"):
            dilated_attention(
                q[:, :1], k, v, self.SLS, self.DRS, is_causal=True, offset=3
            )


def test_longnet_decoder_incremental_matches_full(rng):
    """LongNetDecoder eager stepwise generation == full-sequence forward
    (reference ``LongNetDecoder``, model/LongNet.py:30-45)."""
    from gigapath_tpu.architecture.config import DecoderConfig
    from gigapath_tpu.models.longnet import LongNetDecoder

    cfg = DecoderConfig(
        decoder_embed_dim=32,
        decoder_attention_heads=4,
        decoder_ffn_embed_dim=64,
        decoder_layers=2,
        vocab_size=50,
        dropout=0.0,
        drop_path_rate=0.0,
        segment_length=[4, 16],
        dilated_ratio=[1, 2],
        flash_attention=True,
    )
    dec = LongNetDecoder(cfg)
    T = 9
    tokens = jnp.asarray(rng.integers(0, 50, (2, T)), jnp.int32)
    variables = dec.init(jax.random.PRNGKey(0), tokens, decode=True)
    params, cache = variables["params"], variables["cache"]
    full = dec.apply({"params": params}, tokens)["decoder_out"]

    step_outs = []
    for t in range(T):
        out, mods = dec.apply(
            {"params": params, "cache": cache},
            tokens[:, t : t + 1],
            decode=True,
            mutable=["cache"],
        )
        cache = mods["cache"]
        step_outs.append(out["decoder_out"][:, 0])
    stepped = jnp.stack(step_outs, axis=1)
    np.testing.assert_allclose(np.asarray(full), np.asarray(stepped), atol=2e-4)


class TestBHLDFastPath:
    """Head-major (BHLD) fast path == generic path / numpy oracle.

    On CPU the auto-dispatch in ``dilated_attention`` never takes this path
    (it is TPU-only), so these tests call ``dilated_attention_bhld``
    directly — jnp tier and Pallas tier (interpret mode) both.
    """

    CASES = [
        ([(4, 1), (8, 2), (16, 4)], 32, 8),
        ([(8, 4)], 16, 2),
        ([(6, 2)], 13, 4),
        ([(64, 1), (128, 2), (512, 4)], 523, 12),
    ]

    @pytest.mark.parametrize("branches,N,H", CASES)
    def test_jnp_tier_matches_oracle(self, rng, branches, N, H):
        from gigapath_tpu.ops.dilated_attention import dilated_attention_bhld

        q, k, v = (rng.normal(size=(2, N, H, 4)).astype(np.float32) for _ in range(3))
        out = dilated_attention_bhld(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            [sl for sl, _ in branches], [r for _, r in branches],
            use_pallas=False,
        )
        ref = _np_dilated_oracle(q, k, v, branches)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=1e-4)

    @pytest.mark.parametrize("branches,N,H", CASES[:2])
    def test_pallas_tier_matches_oracle(self, rng, branches, N, H):
        from gigapath_tpu.ops.dilated_attention import dilated_attention_bhld

        q, k, v = (rng.normal(size=(2, N, H, 4)).astype(np.float32) for _ in range(3))
        out = dilated_attention_bhld(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            [sl for sl, _ in branches], [r for _, r in branches],
            use_pallas=True, interpret=True,
        )
        ref = _np_dilated_oracle(q, k, v, branches)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=1e-4)

    def test_valid_len_matches_generic(self, rng):
        from gigapath_tpu.ops.dilated_attention import dilated_attention_bhld

        q, k, v = (jnp.asarray(rng.normal(size=(2, 40, 4, 8)), jnp.float32) for _ in range(3))
        ref = dilated_attention(q, k, v, [8, 16], [1, 2], valid_len=29)
        out = dilated_attention_bhld(q, k, v, [8, 16], [1, 2], valid_len=29, use_pallas=False)
        np.testing.assert_allclose(
            np.asarray(out[:, :29]), np.asarray(ref[:, :29]), atol=2e-5, rtol=1e-4
        )

    def test_traced_valid_len_matches_generic(self, rng):
        """TRACED per-batch valid lengths ride the Pallas tier (SMEM
        counts) — the fine-tune train path's masked batches must not fall
        back to the generic dense-probability tier."""
        from gigapath_tpu.ops.dilated_attention import dilated_attention_bhld

        q, k, v = (jnp.asarray(rng.normal(size=(2, 40, 4, 8)), jnp.float32) for _ in range(3))
        vlen = jnp.asarray([29, 37], jnp.int32)
        ref = dilated_attention(q, k, v, [8, 16], [1, 2], valid_len=vlen)
        out = jax.jit(
            lambda q, k, v, vl: dilated_attention_bhld(
                q, k, v, [8, 16], [1, 2], valid_len=vl,
                use_pallas=True, interpret=True,
            )
        )(q, k, v, vlen)
        for b, n in enumerate([29, 37]):
            np.testing.assert_allclose(
                np.asarray(out[b, :n]), np.asarray(ref[b, :n]),
                atol=2e-5, rtol=1e-4,
            )

    def test_traced_valid_len_gradients(self, rng):
        from gigapath_tpu.ops.dilated_attention import dilated_attention_bhld

        q, k, v = (jnp.asarray(rng.normal(size=(1, 24, 4, 8)), jnp.float32) for _ in range(3))
        vlen = jnp.asarray([17], jnp.int32)

        def loss_p(q):
            o = dilated_attention_bhld(
                q, k, v, [8, 16], [1, 2], valid_len=vlen,
                use_pallas=True, interpret=True,
            )
            return (o[:, :17] ** 2).sum()

        def loss_r(q):
            o = dilated_attention(q, k, v, [8, 16], [1, 2], valid_len=vlen)
            return (o[:, :17] ** 2).sum()

        g1, g2 = jax.grad(loss_p)(q), jax.grad(loss_r)(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=2e-4, rtol=1e-3)

    def test_causal_matches_generic(self, rng):
        from gigapath_tpu.ops.dilated_attention import dilated_attention_bhld

        q, k, v = (jnp.asarray(rng.normal(size=(1, 32, 4, 8)), jnp.float32) for _ in range(3))
        ref = dilated_attention(q, k, v, [8, 32], [1, 2], is_causal=True)
        out = dilated_attention_bhld(q, k, v, [8, 32], [1, 2], is_causal=True, use_pallas=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)

    def test_gradients_match_generic(self, rng):
        from gigapath_tpu.ops.dilated_attention import dilated_attention_bhld

        q, k, v = (jnp.asarray(rng.normal(size=(1, 24, 4, 8)), jnp.float32) for _ in range(3))

        def loss_bhld(q):
            return dilated_attention_bhld(
                q, k, v, [8, 16], [1, 2], use_pallas=True, interpret=True
            ).sum()

        def loss_ref(q):
            return dilated_attention(q, k, v, [8, 16], [1, 2]).sum()

        g1, g2 = jax.grad(loss_bhld)(q), jax.grad(loss_ref)(q)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2), atol=2e-4, rtol=1e-3)


class TestFusedPhaseMajorPath:
    """Phase-major fused kernels (pallas_dilated.py) == oracle/generic path.

    CPU-only via interpret mode; on TPU these kernels back
    ``dilated_attention_fused``.
    """

    @pytest.mark.parametrize(
        "branches,N,H",
        [
            ([(4, 1), (8, 2), (16, 4)], 32, 8),
            ([(64, 1), (128, 2), (512, 4)], 523, 16),
        ],
    )
    def test_matches_oracle(self, rng, branches, N, H):
        from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

        q, k, v = (rng.normal(size=(2, N, H, 4)).astype(np.float32) for _ in range(3))
        out = dilated_attention_fused(
            jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
            [sl for sl, _ in branches], [r for _, r in branches],
            interpret=True,
        )
        ref = _np_dilated_oracle(q, k, v, branches)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5, rtol=1e-4)

    def test_traced_valid_len_matches_static(self, rng):
        """A TRACED per-batch valid length (collate pad masks) must ride
        the fused kernels' SMEM tables and match the static-int result —
        forward AND gradients (the fine-tune train path depends on it)."""
        from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

        B, N, H, D = 2, 40, 4, 8
        q, k, v = (
            jnp.asarray(rng.normal(size=(B, N, H, D)), jnp.float32)
            for _ in range(3)
        )
        vl = jnp.asarray([29, 33], jnp.int32)

        def run(q, k, v, valid_len):
            return dilated_attention_fused(
                q, k, v, [8, 16], [1, 2], valid_len=valid_len, interpret=True
            )

        out_t = run(q, k, v, vl)
        for b, n in enumerate((29, 33)):
            out_s = dilated_attention_fused(
                q[b : b + 1], k[b : b + 1], v[b : b + 1], [8, 16], [1, 2],
                valid_len=n, interpret=True,
            )
            np.testing.assert_allclose(
                np.asarray(out_t[b, :n]), np.asarray(out_s[0, :n]),
                atol=2e-5, rtol=1e-4,
            )

        def loss_t(q, k, v):
            return (run(q, k, v, vl)[:, :29] ** 2).sum()

        def loss_s(q, k, v):
            return (run(q, k, v, 29)[:, :29] ** 2).sum()

        g_t = jax.grad(loss_t, argnums=(0, 1, 2))(q, k, v)
        g_s = jax.grad(loss_s, argnums=(0, 1, 2))(q, k, v)
        # batch 0 has valid length 29 in both variants: its gradients agree
        for a, b, name in zip(g_t, g_s, "qkv"):
            assert np.abs(np.asarray(a)).sum() > 0, f"d{name} is vacuously zero"
            np.testing.assert_allclose(
                np.asarray(a[0]), np.asarray(b[0]), atol=2e-5, rtol=1e-4,
                err_msg=f"d{name} traced != static on batch 0",
            )

    def test_valid_len_and_causal_match_generic(self, rng):
        from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

        q, k, v = (jnp.asarray(rng.normal(size=(2, 40, 4, 8)), jnp.float32) for _ in range(3))
        ref = dilated_attention(q, k, v, [8, 16], [1, 2], valid_len=29)
        out = dilated_attention_fused(q, k, v, [8, 16], [1, 2], valid_len=29, interpret=True)
        np.testing.assert_allclose(
            np.asarray(out[:, :29]), np.asarray(ref[:, :29]), atol=2e-5, rtol=1e-4
        )
        ref_c = dilated_attention(q, k, v, [8, 32], [1, 2], is_causal=True)
        out_c = dilated_attention_fused(q, k, v, [8, 32], [1, 2], is_causal=True, interpret=True)
        np.testing.assert_allclose(np.asarray(out_c), np.asarray(ref_c), atol=2e-5, rtol=1e-4)

    def test_gradients_match_generic(self, rng):
        from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

        q, k, v = (jnp.asarray(rng.normal(size=(1, 24, 4, 8)), jnp.float32) for _ in range(3))
        for arg in range(3):
            def loss_f(x, arg=arg):
                a = [q, k, v]
                a[arg] = x
                return dilated_attention_fused(*a, [8, 16], [1, 2], interpret=True).sum()

            def loss_r(x, arg=arg):
                a = [q, k, v]
                a[arg] = x
                return dilated_attention(*a, [8, 16], [1, 2]).sum()

            g1, g2 = jax.grad(loss_f)([q, k, v][arg]), jax.grad(loss_r)([q, k, v][arg])
            np.testing.assert_allclose(
                np.asarray(g1), np.asarray(g2), atol=2e-4, rtol=1e-3
            )

    def test_odd_ratio_falls_back(self, rng):
        """A ratio not dividing H routes through the head-major branch."""
        from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

        q, k, v = (jnp.asarray(rng.normal(size=(1, 24, 4, 8)), jnp.float32) for _ in range(3))
        out = dilated_attention_fused(q, k, v, [8, 12], [1, 3], interpret=True)
        ref = dilated_attention(q, k, v, [8, 12], [1, 3])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_streaming_fusion_matches_stacked(rng):
    """Online-over-branches fusion must be numerically identical to the
    stacked LSE-softmax fusion. (It enables the long-context envelope; its
    accumulator deliberately KEEPS the branch [B,H,L,D] layout — a
    lane-clean [B,L,H,D] accumulator was tried in round 4 and regressed
    256k from 12.7 GB to an OOM, see the comment in the streaming block.)"""
    from gigapath_tpu.ops.dilated_attention import dilated_attention_bhld

    B, L, H, Dh = 1, 512, 4, 16
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, L, H, Dh)), jnp.float32)
        for _ in range(3)
    )
    kwargs = dict(
        segment_lengths=[128, 256, 512], dilated_ratios=[1, 2, 4],
        valid_len=500, interpret=True,
    )
    stacked = dilated_attention_bhld(q, k, v, streaming_fusion=False, **kwargs)
    streamed = dilated_attention_bhld(q, k, v, streaming_fusion=True, **kwargs)
    np.testing.assert_allclose(
        np.asarray(streamed), np.asarray(stacked), atol=2e-6, rtol=1e-5
    )


def test_fused_streaming_matches_stacked(rng):
    """Fused-path online-over-branches fusion == stacked fusion (the
    long-context memory mode on the default kernel path)."""
    from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

    B, L, H, Dh = 1, 64, 4, 8
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, L, H, Dh)), jnp.float32)
        for _ in range(3)
    )
    kwargs = dict(
        segment_lengths=[16, 32, 64], dilated_ratios=[1, 2, 4],
        valid_len=60, interpret=True,
    )
    stacked = dilated_attention_fused(q, k, v, streaming_fusion=False, **kwargs)
    streamed = dilated_attention_fused(q, k, v, streaming_fusion=True, **kwargs)
    np.testing.assert_allclose(
        np.asarray(streamed)[:, :60], np.asarray(stacked)[:, :60],
        atol=2e-6, rtol=1e-5,
    )


def _branch_both_bodies(q, k, v, sl, r, H, real_len=None, valid_len_dyn=None):
    """One branch through the dispatch as it is (overlapped, non-causal)
    and through the same steps with the serial body asked for by the
    forward's internal argument."""
    import gigapath_tpu.ops.pallas_dilated as pd

    call = lambda a, b, c: pd.dilated_branch_attention(
        a, b, c, sl, r, H, interpret=True, real_len=real_len,
        valid_len_dyn=valid_len_dyn)
    assert "dilated_fwd_overlap" in str(jax.make_jaxpr(call)(q, k, v))
    L = q.shape[1]
    old = pd._dilated_branch_fwd_impl(
        q, k, v, valid_len_dyn, sl, r, H, L if real_len is None else min(real_len, L),
        False, True, pd.PipelineFlags(), body="serial")[:2]
    return call(q, k, v), old


def _assert_same_branch(new, old):
    (o1, l1), (o0, l0) = new, old
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o0), atol=2e-6, rtol=1e-5)
    fin = np.asarray(l0) > -1e19  # uncovered slots hold sentinels
    assert np.array_equal(np.asarray(l1) > -1e19, fin)
    np.testing.assert_allclose(
        np.asarray(l1)[fin], np.asarray(l0)[fin], atol=2e-6, rtol=1e-5
    )


@pytest.mark.slow
@pytest.mark.parametrize(
    "L,sl,r,rl",
    [
        (300, 64, 1, 300),      # nk == 1, single head band
        (300, 64, 2, 277),      # nk == 1, phases + ragged tail
        (1280, 1280, 1, 1280),  # nk > 1 (blocks of 640)
        (1280, 1280, 2, 1100),  # phases + ragged tail at a wider block
    ],
)
def test_overlapped_fwd_matches_serial(rng, L, sl, r, rl):
    """The overlapped forward (what the dispatch picks for a non-causal
    branch) == the serial kernel.

    The overlapped body holds two heads a step and each head's rows in two
    chunks, the next chain's logits emitted before the current chain's
    softmax; the arithmetic of a row is the serial body's."""
    H, Dh = 8, 16
    E = H * Dh
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, L, E)), jnp.float32) for _ in range(3)
    )
    _assert_same_branch(*_branch_both_bodies(q, k, v, sl, r, H, real_len=rl))


@pytest.mark.slow
@pytest.mark.parametrize(
    "L,sl,r,rl",
    [
        (300, 64, 2, 277),      # multi-segment, phases, ragged tail
        (1280, 1280, 1, 1280),  # bwd pipe block_k 512 -> nk=3
        (1280, 1280, 2, 1100),
        (300, 64, 2, "traced"),  # TRACED per-batch valid lengths (the
        #                          collate pad-mask mode of the train path)
    ],
)
def test_pipelined_bwd_matches_serial(rng, monkeypatch, L, sl, r, rl):
    """GIGAPATH_PIPELINED_BWD gradients == the serial backward kernels to
    fp32 rounding (the pipelined kernels fold scale*log2(e) into q before
    the logits matmul, as the forward does, instead of scaling the
    [bq, bk] tile)."""
    from gigapath_tpu.ops.pallas_dilated import dilated_branch_attention

    H, Dh = 8, 16
    E = H * Dh
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, L, E)), jnp.float32) for _ in range(3)
    )
    mask_kw = (
        {"valid_len_dyn": jnp.asarray([L, 211], jnp.int32)}
        if rl == "traced"
        else {"real_len": rl}
    )

    def loss(q_, k_, v_):
        o, _ = dilated_branch_attention(
            q_, k_, v_, sl, r, H, interpret=True, **mask_kw
        )
        return (o * o).sum()

    monkeypatch.delenv("GIGAPATH_PIPELINED_BWD", raising=False)
    g0 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("GIGAPATH_PIPELINED_BWD", "1")
    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g0):
        scale = max(float(jnp.max(jnp.abs(np.asarray(b)))), 1e-12)
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-6
        )


def test_overlapped_fwd_fast_small_geometry(rng):
    """Fast default-tier sibling of test_overlapped_fwd_matches_serial:
    one L=300/nk==1 case so ``pytest -q`` holds the dispatch's forward body
    to the serial one on every run."""
    L, sl, r, rl = 300, 64, 1, 300
    H, Dh = 8, 16
    E = H * Dh
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, L, E)), jnp.float32) for _ in range(3)
    )
    _assert_same_branch(*_branch_both_bodies(q, k, v, sl, r, H, real_len=rl))


def _packed_reference(q6, k6, v6, kvlen):
    """Plain numpy (float64) attention over packed [B, S, r, hb, M, Dh] arrays with
    [B, S, r] valid key counts: (out, lse) as the kernels define them where
    a row has a valid key."""
    q6, k6, v6 = (np.asarray(x, np.float64) for x in (q6, k6, v6))
    M, Dh = q6.shape[-2:]
    s = np.einsum("...qd,...kd->...qk", q6, k6) * Dh ** -0.5
    ok = np.arange(M)[None, None, None, :] < np.asarray(kvlen)[..., None]
    s = np.where(ok[:, :, :, None, None, :], s, -np.inf)
    with np.errstate(invalid="ignore", divide="ignore"):
        m = s.max(-1, keepdims=True)
        p = np.exp(s - m)
        l = p.sum(-1, keepdims=True)
        out = np.einsum("...qk,...kd->...qd", p / l, v6)
        lse = (m + np.log(l))[..., 0]  # [B, S, r, hb, M]
    return out, np.moveaxis(lse, 3, 4)  # lse as [B, S, r, M, hb]


@pytest.mark.parametrize(
    "hb,M,block,dtype,tol",
    [
        (1, 128, 128, jnp.float32, 2e-5),    # a band of one head, nk == 1
        (2, 128, 128, jnp.bfloat16, 2e-2),   # one pair a step, nk == 1
        (3, 256, 128, jnp.float32, 2e-5),    # an odd band: one head a step, nk == 2
        (4, 384, 128, jnp.float32, 2e-5),    # two pairs, nk == 3: the online carry
        (2, 384, 128, jnp.bfloat16, 2e-2),   # the same in the kernels' own precision
    ],
)
def test_overlapped_body_against_serial_and_plain_reference(rng, hb, M, block, dtype, tol):
    """The forward bodies over packed arrays, key counts ragged (full,
    partial, a block wholly past the count, no key at all): the overlapped
    body gives the serial body's numbers, and both the plain reference's."""
    import gigapath_tpu.ops.pallas_dilated as pd

    B, S, r, Dh = 2, 2, 2, 16
    shape = (B, S, r, hb, M, Dh)
    q6, k6, v6 = (jnp.asarray(rng.normal(size=shape), dtype) for _ in range(3))
    kvlen = rng.integers(1, M + 1, size=(B, S, r)).astype(np.int32)
    kvlen[0, 0, 0], kvlen[1, 1, 1], kvlen[0, 1, 0] = 0, M, min(M, block - 5)
    kvlen = jnp.asarray(kvlen)
    plan = pd.plan_fwd_body(False, hb, block)
    assert plan == pd.FwdPlan("overlap", 2 if hb % 2 == 0 else 1, block // 2)
    new = pd._packed_forward(q6, k6, v6, kvlen, False, hb, Dh, block, True)
    old = pd._packed_forward(q6, k6, v6, kvlen, False, hb, Dh, block, True, body="serial")
    for a, b in zip(new, old):
        assert a.shape == b.shape and a.dtype == b.dtype
    np.testing.assert_allclose(
        np.asarray(new[0], np.float32), np.asarray(old[0], np.float32), atol=2e-6, rtol=1e-5)
    # lanes of the band's heads equal; lanes past them are fill in both
    np.testing.assert_allclose(
        np.asarray(new[1])[..., :hb], np.asarray(old[1])[..., :hb], atol=0, rtol=2e-6)
    assert np.all(np.asarray(new[1])[..., hb:] < -1e19)
    ref_out, ref_lse = _packed_reference(q6, k6, v6, kvlen)
    some = (np.asarray(kvlen) > 0)[:, :, :, None, None]
    got = np.asarray(new[0], np.float64)
    np.testing.assert_allclose(
        np.where(some[..., None], got, 0), np.where(some[..., None], ref_out, 0), atol=tol)
    np.testing.assert_allclose(
        np.where(some, np.asarray(new[1])[..., :hb], 0), np.where(some, ref_lse, 0), atol=tol)
    # a band with no valid key: out 0, lse the stats' floor, as the serial body
    assert np.all(got[0, 0, 0] == 0) and np.all(np.asarray(new[1])[0, 0, 0, :, :hb] < -1e19)


@pytest.mark.parametrize("rl", [101, "traced"])
def test_overlapped_fwd_ragged_and_traced_valid_len(rng, rl):
    """A static ragged tail and TRACED per-batch valid lengths (they ride the
    kernels' SMEM tables) through the dispatch's forward body, under jit as
    the train path runs it: the serial body's numbers, and the generic jnp
    branch's."""
    from gigapath_tpu.ops.dilated_attention import dilated_attention

    L, sl, r, H, Dh = 128, 32, 2, 4, 16
    q, k, v = (
        jnp.asarray(rng.normal(size=(2, L, H * Dh)), jnp.float32) for _ in range(3)
    )
    mask_kw = (
        {"valid_len_dyn": jnp.asarray([L, 77], jnp.int32)}
        if rl == "traced" else {"real_len": rl}
    )
    new, old = _branch_both_bodies(q, k, v, sl, r, H, **mask_kw)
    _assert_same_branch(new, old)
    if rl != "traced":
        x4 = lambda x: x.reshape(2, L, H, Dh)
        ref = dilated_attention(x4(q), x4(k), x4(v), [sl], [r], valid_len=rl)
        np.testing.assert_allclose(
            np.asarray(new[0]).reshape(2, L, H, Dh)[:, :rl], np.asarray(ref)[:, :rl],
            atol=2e-5, rtol=1e-4)


def test_gradients_through_the_overlapped_fwd(rng, monkeypatch):
    """The forward's packed (out, lse) are the backward's residuals: the
    gradients through the overlapped body are the ones through the serial."""
    import gigapath_tpu.ops.pallas_dilated as pd

    L, sl, r, rl = 128, 32, 2, 101
    H, Dh = 4, 16
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, L, H * Dh)), jnp.float32) for _ in range(3)
    )

    def loss(q_, k_, v_):
        o, _ = pd.dilated_branch_attention(
            q_, k_, v_, sl, r, H, real_len=rl, interpret=True)
        return (o * o).sum()

    grads = lambda: jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    assert "dilated_fwd_overlap" in str(jax.make_jaxpr(jax.grad(loss))(q, k, v))
    g1 = grads()
    # the planner is the one place that chooses: turn it, and drop the traces
    # made under the other choice (a trace is cached by the function, not by
    # what the planner said)
    with monkeypatch.context() as mp:
        mp.setattr(pd, "plan_fwd_body",
                   lambda causal, hb, block: pd.FwdPlan("serial", 1, block))
        jax.clear_caches()
        assert "dilated_fwd_overlap" not in str(jax.make_jaxpr(jax.grad(loss))(q, k, v))
        g0 = grads()
    jax.clear_caches()
    for a, b in zip(g1, g0):
        scale = max(float(jnp.max(jnp.abs(b))), 1e-12)
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-6)


@pytest.mark.parametrize(
    "L,expected",
    [
        # the benchmark's slides: 10,240 tiles + the class token, padded
        (10368, {1: (16, 1024, 1), 2: (8, 1024, 3), 4: (4, 896, 3), 8: (2, 768, 2),
                 16: (1, 768, 1)}),
        # a serve bucket: every branch past the second is one segment
        (4224, {1: (16, 1024, 1), 2: (8, 768, 3), 4: (4, 640, 2), 8: (2, 640, 1),
                16: (1, 384, 1)}),
    ],
)
def test_fwd_plan_at_the_flagship_schedule(L, expected):
    """The forward body is chosen from shapes, in one place: at the
    flagship's E = 768 and 16 heads of 48 every non-causal branch takes the
    overlapped body, two heads a step where the band's count is even
    (r1-r8) and one where the band is one head (r16), rows in two chunks;
    a causal branch takes the serial body."""
    import gigapath_tpu.ops.pallas_dilated as pd
    from gigapath_tpu.models.longnet_config import flagship_geometry

    geom = flagship_geometry()
    H, Dh = geom["heads"], geom["head_dim"]
    assert (H, Dh) == (16, 48)
    for sl, r in zip(geom["segment_lengths"], geom["dilated_ratios"]):
        g, S, gp, m, Mp, block = pd._branch_geometry(L, H * Dh, sl, r)
        hb = H // r
        assert (hb, block, Mp // block) == expected[r], (r, hb, block, Mp // block)
        plan = pd.plan_fwd_body(False, hb, block)
        assert plan == pd.FwdPlan("overlap", 2 if r < 16 else 1, block // 2), r
        assert pd.plan_fwd_body(True, hb, block) == pd.FwdPlan("serial", 1, block)


def test_pipelined_bwd_fast_small_geometry(rng, monkeypatch):
    """Fast default-tier sibling of test_pipelined_bwd_matches_serial
    (GIGAPATH_PIPELINED_BWD): one small multi-phase ragged-tail case."""
    from gigapath_tpu.ops.pallas_dilated import dilated_branch_attention

    L, sl, r, rl = 128, 32, 2, 101
    H, Dh = 4, 16
    E = H * Dh
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, L, E)), jnp.float32) for _ in range(3)
    )

    def loss(q_, k_, v_):
        o, _ = dilated_branch_attention(
            q_, k_, v_, sl, r, H, real_len=rl, interpret=True
        )
        return (o * o).sum()

    monkeypatch.delenv("GIGAPATH_PIPELINED_BWD", raising=False)
    g0 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    monkeypatch.setenv("GIGAPATH_PIPELINED_BWD", "1")
    g1 = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g0):
        scale = max(float(jnp.max(jnp.abs(np.asarray(b)))), 1e-12)
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-6
        )


def test_seq_parallel_fused_routing_fast(rng, monkeypatch):
    """Fast default-tier sibling of the seq-parallel fused-routing slow
    tests: a 2-device mesh at tiny geometry still routes fits-local
    branches through the fused kernels and matches single-device."""
    import functools

    from jax.sharding import Mesh, PartitionSpec as P

    import gigapath_tpu.ops.flash_attention as fa
    import gigapath_tpu.ops.pallas_dilated as pdm
    from gigapath_tpu.ops import dilated_attention as da

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    real = pdm.dilated_branch_attention
    routed = []

    def spy(q, k, v, sl, r, H, **kw):
        routed.append((sl, r, kw.get("real_len")))
        kw["interpret"] = True
        return real(q, k, v, sl, r, H, **kw)

    monkeypatch.setattr(pdm, "dilated_branch_attention", spy)

    n_dev = 2
    B, L, H, Dh = 1, 64, 4, 8
    sls, drs = [8, 32], [1, 2]  # both fit the 32-token local shard
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, L, H, Dh)), jnp.float32)
        for _ in range(3)
    )
    single = da.dilated_attention(q, k, v, sls, drs)
    routed.clear()

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("seq",))
    # vma checking can't see through pallas_call —
    # disabled exactly as in the slow seq-parallel tests
    from jax import shard_map

    fn = shard_map(
        functools.partial(
            da.dilated_attention, segment_lengths=sls, dilated_ratios=drs,
            seq_axis_name="seq", seq_axis_size=n_dev,
        ),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    )
    sharded = fn(q, k, v)
    assert len(routed) == len(sls), (
        f"both local branches should take the fused path, got {routed}"
    )
    assert all(rl == L // n_dev for _, _, rl in routed)
    np.testing.assert_allclose(
        np.asarray(sharded), np.asarray(single), atol=2e-5, rtol=1e-4
    )


@pytest.mark.slow
def test_seq_parallel_local_branches_use_fused_path(rng, monkeypatch):
    """Under sequence parallelism, branches whose segment fits the local
    shard route through the fused phase-major kernels (the single-chip
    default) and still match the single-device result. _on_tpu is
    monkeypatched True with interpret-mode kernels so the TPU-only
    dispatch runs on the CPU mesh."""
    import functools

    from jax.sharding import Mesh, PartitionSpec as P

    import gigapath_tpu.ops.flash_attention as fa
    import gigapath_tpu.ops.pallas_dilated as pdm
    from gigapath_tpu.ops import dilated_attention as da

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    real = pdm.dilated_branch_attention
    routed = []

    def spy(q, k, v, sl, r, H, **kw):
        routed.append((sl, r, kw.get("real_len")))
        kw["interpret"] = True
        return real(q, k, v, sl, r, H, **kw)

    monkeypatch.setattr(pdm, "dilated_branch_attention", spy)

    n_dev = 8
    B, L, H, Dh = 1, 1024, 4, 8
    sls, drs = [32, 128], [1, 2]
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, L, H, Dh)), jnp.float32)
        for _ in range(3)
    )
    single = da.dilated_attention(q, k, v, sls, drs)
    assert routed, "single-device fast path should also route via the spy"
    routed.clear()

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("seq",))
    fn = jax.shard_map(
        functools.partial(
            da.dilated_attention, segment_lengths=sls, dilated_ratios=drs,
            seq_axis_name="seq", seq_axis_size=n_dev,
        ),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        # jax 0.9's vma checking cannot yet see through pallas_call
        # (out_shape avals carry no vma); jax's own guidance is
        # check_vma=False for shard_map regions hosting pallas kernels
        check_vma=False,
    )
    sharded = fn(q, k, v)
    assert len(routed) == len(sls), (
        f"both local branches should take the fused path, got {routed}"
    )
    assert all(rl == L // n_dev for _, _, rl in routed)
    np.testing.assert_allclose(
        np.asarray(sharded), np.asarray(single), atol=2e-5, rtol=1e-4
    )


@pytest.mark.slow
def test_seq_parallel_mixed_fused_and_gathered_branches(rng, monkeypatch):
    """One cross-branch softmax fusion mixing a fused-kernel local branch
    (Pallas lse convention) with a gathered branch computed by the generic
    path (sparse_to_dense lse) must match the single-device result — the
    two lse conventions may never drift apart. The gathered branch's
    sparse length stays under PALLAS_MIN_SEQ so it runs the jnp tier even
    with _on_tpu patched True."""
    import functools

    from jax.sharding import Mesh, PartitionSpec as P

    import gigapath_tpu.ops.flash_attention as fa
    import gigapath_tpu.ops.pallas_dilated as pdm
    from gigapath_tpu.ops import dilated_attention as da

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    real = pdm.dilated_branch_attention
    routed = []

    def spy(q, k, v, sl, r, H, **kw):
        routed.append(sl)
        kw["interpret"] = True
        return real(q, k, v, sl, r, H, **kw)

    monkeypatch.setattr(pdm, "dilated_branch_attention", spy)

    n_dev = 8
    B, L, H, Dh = 1, 1024, 4, 8
    sls, drs = [32, 512], [1, 2]  # 512 > local 128 -> gathered, m=256 jnp tier
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, L, H, Dh)), jnp.float32)
        for _ in range(3)
    )
    single = da.dilated_attention(q, k, v, sls, drs)
    routed.clear()

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("seq",))
    fn = jax.shard_map(
        functools.partial(
            da.dilated_attention, segment_lengths=sls, dilated_ratios=drs,
            seq_axis_name="seq", seq_axis_size=n_dev,
        ),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
        check_vma=False,
    )
    sharded = fn(q, k, v)
    assert routed == [32], f"only the local branch routes fused, got {routed}"
    np.testing.assert_allclose(
        np.asarray(sharded), np.asarray(single), atol=2e-5, rtol=1e-4
    )


def test_seq_parallel_oversized_segments_clamp_to_global_length(rng):
    """A segment longer than the whole sharded sequence is one segment over
    all of it (the single-device ``g = min(sl, L)``), whether or not it
    divides into whole shards — 725 does not, 4096 exceeds the seq axis.
    Before the clamp the gather asserted (the flagship's 185,363) or sliced
    past the axis (1,048,576)."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from gigapath_tpu.ops import dilated_attention as da

    n_dev, N, H, Dh = 4, 128, 8, 4
    sls, drs = [16, 32, 725, 4096], [1, 2, 4, 8]
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, N, H, Dh)), jnp.float32) for _ in range(3)
    )
    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("seq",))
    fn = shard_map(
        lambda q, k, v: da.dilated_attention(
            q, k, v, sls, drs, seq_axis_name="seq", seq_axis_size=n_dev
        ),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3, out_specs=P(None, "seq"),
        check_vma=False,
    )
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)), np.asarray(da.dilated_attention(q, k, v, sls, drs)),
        atol=1e-5,
    )


def test_seq_parallel_warns_when_a_local_segment_does_not_divide_the_shard(
    rng, monkeypatch
):
    """Each shard segments its own tokens: a local segment that does not
    divide the shard (the flagship's 5,792 in a 16,384-token shard) gives
    another result than the unsharded op, and says so once."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from gigapath_tpu.ops import dilated_attention as da

    warned = []
    monkeypatch.setattr(da, "_warn_once", warned.append)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 4)), jnp.float32)
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    fn = shard_map(
        lambda q: da.dilated_attention(
            q, q, q, [12, 16], [1, 2], seq_axis_name="seq", seq_axis_size=2
        ),
        mesh=mesh, in_specs=(P(None, "seq"),), out_specs=P(None, "seq"),
        check_vma=False,
    )
    assert np.isfinite(np.asarray(fn(q))).all()
    assert len(warned) == 1 and "12 does not divide the 32-token shard" in warned[0]


@pytest.mark.slow
def test_seq_parallel_vma_checked_falls_back_generic(rng, monkeypatch):
    """Inside a DEFAULT (check_vma=True) shard_map the fused-local routing
    must auto-fall-back to the generic path (pallas is vma-opaque in
    jax 0.9) instead of hard-failing existing callers."""
    import functools

    from jax.sharding import Mesh, PartitionSpec as P

    import gigapath_tpu.ops.flash_attention as fa
    import gigapath_tpu.ops.pallas_dilated as pdm
    from gigapath_tpu.ops import dilated_attention as da

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    real = pdm.dilated_branch_attention

    def interp(q, k, v, sl, r, H, **kw):
        kw["interpret"] = True
        return real(q, k, v, sl, r, H, **kw)

    monkeypatch.setattr(pdm, "dilated_branch_attention", interp)

    n_dev = 8
    B, L, H, Dh = 1, 512, 4, 8
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, L, H, Dh)), jnp.float32)
        for _ in range(3)
    )
    single = da.dilated_attention(q, k, v, [32], [1])

    def boom(*a, **kw):
        raise AssertionError("fused path must not run under check_vma=True")

    monkeypatch.setattr(pdm, "dilated_branch_attention", boom)

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("seq",))
    fn = jax.shard_map(
        functools.partial(
            da.dilated_attention, segment_lengths=[32], dilated_ratios=[1],
            seq_axis_name="seq", seq_axis_size=n_dev,
        ),
        mesh=mesh,
        in_specs=(P(None, "seq"), P(None, "seq"), P(None, "seq")),
        out_specs=P(None, "seq"),
    )
    sharded = fn(q, k, v)  # must not raise
    np.testing.assert_allclose(
        np.asarray(sharded), np.asarray(single), atol=2e-5, rtol=1e-4
    )



# ---------------------------------------------------------------------------
# cross-branch merge epilogue over the packed results
# ---------------------------------------------------------------------------

_FLAGSHIP_SEGS = [1024, 5792, 32768, 185363, 1048576]
_FLAGSHIP_RATIOS = [1, 2, 4, 8, 16]


def _dense_path(q, k, v, sls, drs, valid_len=None):
    """The fused op's fallback, reached as a function: every branch
    unpacked to dense (out, lse), merged by XLA."""
    from gigapath_tpu.ops import dilated_attention as da
    from gigapath_tpu.ops.pallas_dilated import PipelineFlags

    real_len, valid_dyn = da._normalize_valid_len(valid_len, *q.shape[:2])
    return da._fused_dense_merge(
        q, k, v, sls, drs, is_causal=False, real_len=real_len,
        valid_dyn=valid_dyn, streaming_fusion=False, interpret=True,
        flags=PipelineFlags(),
    )


def _packed_results(rng, plan, B, uncovered_rows=0):
    """Random packed (out6, lse5) per branch of ``plan`` as the kernels
    leave them: lanes past a band's heads hold NEG_INF. With
    ``uncovered_rows``, the first packed rows of every branch read NEG_INF
    on every lane, as a fully masked row does."""
    from gigapath_tpu.ops.pallas_flash import LANES, NEG_INF

    outs, lses = [], []
    for r, hb, S, g, Mp in plan.branches:
        outs.append(jnp.asarray(
            rng.normal(size=(B, S, r, hb, Mp, plan.Dh)), jnp.float32))
        lse = 3.0 * rng.normal(size=(B, S, r, Mp, LANES))
        lse[..., hb:] = NEG_INF
        lse[..., :uncovered_rows, :] = NEG_INF
        lses.append(jnp.asarray(lse, jnp.float32))
    return tuple(outs), tuple(lses)


def _dense_merge_of_packed(outs, lses, plan, sls):
    """unpack + lse scatter + stacked softmax merge of packed results: what
    the epilogue replaces, on the same inputs."""
    from gigapath_tpu.ops import pallas_dilated as pdm
    from gigapath_tpu.utils.kernel_checks import _jnp_unpack

    L, E, H = plan.L, plan.E, plan.H
    B = outs[0].shape[0]
    dense, tables = [], []
    for (r, hb, S, g, Mp), o6, l5, sl in zip(plan.branches, outs, lses, sls):
        m = pdm._branch_geometry(L, E, int(sl), r)[3]
        # the plain jnp unpack: the copy kernel has no VJP of its own
        dense.append(_jnp_unpack(o6, L, E, g, S, r))
        tables.append(pdm._scatter_lse(l5, B, L, H, g, S, r, m))
    weights = jax.nn.softmax(jnp.stack(tables), axis=0)  # [n, B, H, L]
    acc = 0.0
    for o, w in zip(dense, weights):
        acc = acc + o.reshape(B, L, H, plan.Dh) * w.transpose(0, 2, 1)[..., None]
    return acc.reshape(B, L, E)


class TestStreamFusionEpilogue:
    """Interpret-mode parity of the packed merge epilogue (what
    ``dilated_attention_fused`` runs wherever a plan exists) against the
    dense unpack + stacked-softmax path (its fallback, reached as a
    function). Fast default tier: every ``pytest -q`` verifies the epilogue
    without a chip."""

    def _qkv(self, rng, B, L, H, Dh, dtype=jnp.float32):
        return tuple(
            jnp.asarray(rng.normal(size=(B, L, H, Dh)), dtype)
            for _ in range(3)
        )

    def _paths(self, q, k, v, sls, drs, **kw):
        from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

        dense = _dense_path(q, k, v, sls, drs, **kw)
        epilogue = dilated_attention_fused(
            q, k, v, sls, drs, interpret=True, **kw
        )
        return dense, epilogue

    def _grads(self, op, q, k, v):
        def loss(q, k, v):
            return (op(q, k, v).astype(jnp.float32) ** 2).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    def test_fwd_parity_ragged_tail(self, rng):
        """ISSUE geometry: L=300, 2 branches, ragged tail: the epilogue's
        forward within 1e-5 of the dense path."""
        q, k, v = self._qkv(rng, 1, 300, 4, 8)
        dense, epilogue = self._paths(q, k, v, [256, 512], [1, 2], valid_len=277)
        np.testing.assert_allclose(
            np.asarray(epilogue), np.asarray(dense), atol=1e-5, rtol=1e-5
        )

    def test_fwd_parity_uncovered_slots(self, rng):
        """No r=1 branch: (token, head) slots covered by NO branch must
        produce the same (zero) output as the dense path's uniform-softmax-
        over-NEG_INF convention."""
        q, k, v = self._qkv(rng, 1, 128, 4, 8)
        dense, epilogue = self._paths(q, k, v, [64, 128], [2, 4])
        np.testing.assert_allclose(
            np.asarray(epilogue), np.asarray(dense), atol=1e-5, rtol=1e-5
        )
        # uncovered slots exist and are exactly zero on both paths
        assert (np.asarray(dense) == 0).any()
        assert ((np.asarray(dense) == 0) == (np.asarray(epilogue) == 0)).all()

    def test_grad_parity_ragged_tail(self, rng):
        """Epilogue backward (packed d_out per branch via re-derived
        weights) within 1e-4 of the dense path's gradients."""
        from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

        q, k, v = self._qkv(rng, 1, 300, 4, 8)
        vl = jnp.asarray([277], jnp.int32)  # traced ragged tail
        g_dense = self._grads(
            lambda q, k, v: _dense_path(q, k, v, [256, 512], [1, 2], vl), q, k, v)
        g_epilogue = self._grads(
            lambda q, k, v: dilated_attention_fused(
                q, k, v, [256, 512], [1, 2], valid_len=vl, interpret=True),
            q, k, v)
        for a, b in zip(g_dense, g_epilogue):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), atol=1e-4, rtol=1e-4
            )

    @pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
    def test_flagship_r2_geometry_straddles_its_segment(self, rng, grad):
        """The flagship's r=2 branch at small width: a segment of
        2^5 * 181 rows beside power-of-two ones, so its segment starts off
        every block grid and one window of the epilogue straddles it (rows
        up to the segment's end from one element window, the next
        segment's first rows from another); L off the block. The epilogue
        alone on random packed results, against unpack + scatter + softmax
        of the same results, forward and gradient."""
        from gigapath_tpu.ops import pallas_dilated as pdm

        sls, drs = _FLAGSHIP_SEGS[:3], _FLAGSHIP_RATIOS[:3]
        L, H, Dh = 5792 + 436, 4, 8
        plan = pdm.plan_stream_fusion(L, H * Dh, H, sls, drs, interpret=True,
                                      itemsize=4)
        assert plan.straddles[0] is None and plan.straddles[2] is None
        (height,) = plan.straddles[1]
        assert 0 < height < plan.BT // 2 and L % plan.BT
        outs, lses = _packed_results(rng, plan, 2)

        def epilogue(outs):
            return pdm._fusion_epilogue(outs, lses, plan)

        def dense(outs):
            return _dense_merge_of_packed(outs, lses, plan, sls)

        if grad:
            probe = jnp.asarray(rng.normal(size=(2, L, H * Dh)), jnp.float32)
            epilogue, dense = (
                jax.grad(lambda outs, f=f: (f(outs) * probe).sum())
                for f in (epilogue, dense)
            )
        for a, b in zip(jax.tree.leaves(dense(outs)),
                        jax.tree.leaves(epilogue(outs))):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), atol=1e-5, rtol=1e-5
            )

    def test_all_uncovered_slots_give_zero(self, rng):
        """Slots no branch covers (no r=1 branch: a token's phase leaves
        head bands out) and rows every branch masked (lse NEG_INF on every
        lane) come out finite: exact zeros where nothing covers, the
        uniform mean of the branches' rows where all are masked, as the
        dense merge gives."""
        from gigapath_tpu.ops import pallas_dilated as pdm

        sls, drs, L, H, Dh = [64, 128], [2, 4], 128, 4, 8
        plan = pdm.plan_stream_fusion(L, H * Dh, H, sls, drs, interpret=True,
                                      itemsize=4)
        outs, lses = _packed_results(rng, plan, 1, uncovered_rows=8)
        got = np.asarray(pdm._fusion_epilogue(outs, lses, plan))
        want = np.asarray(_dense_merge_of_packed(outs, lses, plan, sls))
        assert np.isfinite(got).all()
        np.testing.assert_allclose(got, want, atol=1e-6, rtol=1e-6)
        assert (want == 0).any() and ((want == 0) == (got == 0)).all()

    @pytest.mark.parametrize("L,straddles", [
        (10368, (None, (80,), None, None, None)),   # the benchmark's slides
        (8320, (None, (80,), None, None, None)),    # the fine-tune buckets
        (16512, (None, (32, 80), None, None, None)),
    ])
    def test_plan_of_the_flagship_is_one_pass(self, L, straddles):
        """What L = 10,368, E = 768, H = 16 gets: ONE block of 256 tokens
        that all five branches are read in (one ``pallas_call`` a layer,
        no float32 state between passes), r=2 through element windows."""
        from gigapath_tpu.ops.pallas_dilated import plan_stream_fusion

        plan = plan_stream_fusion(L, 768, 16, _FLAGSHIP_SEGS, _FLAGSHIP_RATIOS)
        assert plan.BT == 256
        assert plan.straddles == straddles
        assert [b[0] for b in plan.branches] == _FLAGSHIP_RATIOS

    def test_default_call_takes_the_epilogue_when_a_plan_exists(
            self, rng, monkeypatch):
        """No flag and no argument: wherever ``plan_stream_fusion`` finds a
        blocking the default call goes through ``_fusion_epilogue``, and an
        explicit ``streaming_fusion=True`` keeps the dense fold."""
        from gigapath_tpu.ops.dilated_attention import dilated_attention_fused
        from gigapath_tpu.ops import pallas_dilated as pdm

        calls = []
        real = pdm._fusion_epilogue

        def spy(outs, lses, plan):
            calls.append(plan)
            return real(outs, lses, plan)

        monkeypatch.setattr(pdm, "_fusion_epilogue", spy)
        q, k, v = self._qkv(rng, 1, 64, 4, 8)
        assert pdm.plan_stream_fusion(64, 32, 4, [32, 64], [1, 2]) is not None
        out = dilated_attention_fused(q, k, v, [32, 64], [1, 2], interpret=True)
        assert len(calls) == 1, "the default call must take the epilogue"
        calls.clear()
        folded = dilated_attention_fused(
            q, k, v, [32, 64], [1, 2], interpret=True, streaming_fusion=True)
        assert not calls
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(folded), atol=1e-5, rtol=1e-5
        )

    def test_no_plan_takes_the_dense_path_and_warns_once(
            self, rng, monkeypatch):
        """A geometry with no legal blocking (g=12 divides no candidate
        block and starts off the sublane tile) gets a plan of ``None``: the
        dense unpack + merge runs, and says so once a schedule."""
        import warnings

        from gigapath_tpu.ops import dilated_attention as da
        from gigapath_tpu.ops import pallas_dilated as pdm

        assert pdm.plan_stream_fusion(24, 32, 4, [12, 32], [1, 2]) is None
        monkeypatch.setattr(
            pdm, "_fusion_epilogue",
            lambda *a: pytest.fail("no plan, yet the epilogue ran"))
        monkeypatch.setattr(da, "_WARNED", set())
        q, k, v = self._qkv(rng, 1, 24, 4, 8)
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            first = da.dilated_attention_fused(
                q, k, v, [12, 32], [1, 2], interpret=True)
            again = da.dilated_attention_fused(
                q, k, v, [12, 32], [1, 2], interpret=True)
        said = [w for w in caught if "admits no merge epilogue" in str(w.message)]
        assert len(said) == 1, [str(w.message) for w in caught]
        dense = _dense_path(q, k, v, [12, 32], [1, 2])
        np.testing.assert_array_equal(np.asarray(first), np.asarray(dense))
        np.testing.assert_array_equal(np.asarray(again), np.asarray(dense))

    def test_infeasible_plan_falls_back_to_dense(self, rng):
        """A ratio that does not divide the heads' lanes, or a single
        branch, gets no plan either."""
        from gigapath_tpu.ops.pallas_dilated import plan_stream_fusion

        assert plan_stream_fusion(64, 32, 4, [64], [1]) is None
        assert plan_stream_fusion(64, 32, 4, [32, 64], [1, 8]) is None
        # a segment shorter than every block its neighbours admit
        assert plan_stream_fusion(48, 16, 2, [24, 64], [1, 2]) is None


def test_epilogue_jaxpr_has_no_dense_branch_lse(rng):
    """Regression guard (acceptance): the traced flagship-style program
    contains NO dense per-branch [B, H, L] lse intermediate, forward or
    under differentiation: the glue cannot silently reappear. The dense
    path is the positive control (it must still materialize them)."""
    from gigapath_tpu.ops.dilated_attention import dilated_attention_fused
    from gigapath_tpu.ops.pallas_dilated import plan_stream_fusion

    B, L, H, Dh = 1, 512, 16, 4
    sls, drs = _FLAGSHIP_SEGS, _FLAGSHIP_RATIOS
    assert plan_stream_fusion(L, H * Dh, H, sls, drs, itemsize=4) is not None
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, L, H, Dh)), jnp.float32)
        for _ in range(3)
    )

    def trace(op, grad=False):
        def f(q, k, v):
            return (op(q, k, v).astype(jnp.float32) ** 2).sum()

        fn = jax.grad(f) if grad else f
        return str(jax.make_jaxpr(fn)(q, k, v))

    dense_lse = f"f32[{B},{H},{L}]"
    for grad in (False, True):
        on = trace(lambda q, k, v: dilated_attention_fused(
            q, k, v, sls, drs, interpret=True), grad)
        off = trace(lambda q, k, v: _dense_path(q, k, v, sls, drs), grad)
        assert dense_lse not in on, (
            f"dense per-branch lse reappeared in the epilogue trace "
            f"(grad={grad})"
        )
        assert dense_lse in off, (
            "positive control broke: the dense path should materialize "
            f"per-branch [B, H, L] lse tensors (grad={grad})"
        )


def test_seq_parallel_ragged_mask_fused_routing(rng, monkeypatch):
    """a ragged key_padding_mask (traced per-shard
    valid counts) under sequence parallelism routes segment-local branches
    through the fused kernels — not the generic fallback — and the
    gathered branch masks its all-gathered keys from the per-rank counts.
    Loss and grads match the single-device result."""
    import functools

    from jax.sharding import Mesh, PartitionSpec as P

    import gigapath_tpu.ops.flash_attention as fa
    import gigapath_tpu.ops.pallas_dilated as pdm
    from gigapath_tpu.ops import dilated_attention as da

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    real = pdm.dilated_branch_attention
    routed = []

    def spy(q, k, v, sl, r, H, **kw):
        routed.append((sl, kw.get("valid_len_dyn") is not None))
        kw["interpret"] = True
        return real(q, k, v, sl, r, H, **kw)

    monkeypatch.setattr(pdm, "dilated_branch_attention", spy)

    n_dev = 2
    B, L, H, Dh = 1, 32, 4, 8
    sls, drs = [8, 32], [1, 2]  # 8 fits the 16-token shard; 32 gathers
    valid = 25
    q, k, v = (
        jnp.asarray(rng.normal(size=(B, L, H, Dh)), jnp.float32)
        for _ in range(3)
    )
    pad_mask = jnp.arange(L)[None, :] >= valid  # True = pad (collate)
    vmask = (~pad_mask).astype(jnp.float32)[:, :, None, None]

    def single_loss(q, k, v):
        out = da.dilated_attention(
            q, k, v, sls, drs,
            valid_len=jnp.full((B,), valid, jnp.int32),
        )
        return ((out.astype(jnp.float32) * vmask) ** 2).sum()

    single = single_loss(q, k, v)
    g_single = jax.grad(single_loss, argnums=(0, 1, 2))(q, k, v)
    assert routed, "single-device fused path must route via the spy"
    routed.clear()

    mesh = Mesh(np.array(jax.devices()[:n_dev]), ("seq",))
    from jax import shard_map

    def local_fn(q, k, v, mask_local):
        # per-shard valid counts from the SHARDED mask — exactly what
        # DilatedAttention._attend derives under shard_map
        vl = (~mask_local).sum(axis=-1).astype(jnp.int32)
        return da.dilated_attention(
            q, k, v, sls, drs, seq_axis_name="seq", seq_axis_size=n_dev,
            valid_len=vl,
        )

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(None, "seq"),) * 3 + (P(None, "seq"),),
        out_specs=P(None, "seq"),
        check_vma=False,
    )

    def sharded_loss(q, k, v):
        out = fn(q, k, v, pad_mask)
        return ((out.astype(jnp.float32) * vmask) ** 2).sum()

    sharded = sharded_loss(q, k, v)
    g_sharded = jax.grad(sharded_loss, argnums=(0, 1, 2))(q, k, v)
    fused_routed = [e for e in routed if e[0] == 8]
    assert fused_routed and all(has_vl for _, has_vl in fused_routed), (
        f"ragged local branch must route fused WITH valid counts: {routed}"
    )
    assert all(sl != 64 for sl, _ in routed), (
        f"the gathered branch must not route through the fused kernels: "
        f"{routed}"
    )
    np.testing.assert_allclose(
        float(sharded), float(single), rtol=1e-5
    )
    for a, b in zip(g_single, g_sharded):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=2e-5, rtol=1e-4
        )


# ---------------------------------------------------------------------------
# ring-scheduled sequence parallelism (GIGAPATH_RING_ATTN)
# ---------------------------------------------------------------------------


def _seq_parallel_fn(mesh, ndev, sls, drs, flags, n_arrays=3):
    """shard_map'd dilated_attention over a seq axis of ``ndev`` ranks."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    return shard_map(
        lambda q, k, v: dilated_attention(
            q, k, v, sls, drs, seq_axis_name="seq", seq_axis_size=ndev,
            flags=flags,
        ),
        mesh=mesh,
        in_specs=(P(None, "seq"),) * n_arrays,
        out_specs=P(None, "seq"),
        check_vma=False,
    )


def _qkv3(rng, B, N, H, D):
    return tuple(
        jnp.asarray(rng.normal(size=(B, N, H, D)), jnp.float32)
        for _ in range(3)
    )


def test_ring_matches_gather_seq_parallel(rng):
    """Core ring acceptance, compact tier: on a 2-way seq mesh the
    ring-scheduled gathered branch matches the all-gather path (the
    parity oracle) AND the single-device op — forward 1e-5, grads 1e-4.
    The 8-way mesh with a sub-mesh segment is the slow-tier sibling
    (test_ring_matches_gather_8way_submesh)."""
    from jax.sharding import Mesh

    from gigapath_tpu.ops.pallas_dilated import PipelineFlags

    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    q, k, v = _qkv3(rng, 1, 16, 4, 8)
    sls, drs = [4, 16], [1, 2]  # 16 > the 8-token shard: rps=2 ring

    ref = dilated_attention(q, k, v, sls, drs)
    gather_fn = _seq_parallel_fn(mesh, 2, sls, drs, PipelineFlags())
    ring_fn = _seq_parallel_fn(
        mesh, 2, sls, drs, PipelineFlags(ring_attn=True)
    )
    out_g = gather_fn(q, k, v)
    out_r = ring_fn(q, k, v)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(out_g), atol=1e-5)
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(ref), atol=1e-5)

    def grads(fn):
        def loss(q, k, v):
            return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(grads(gather_fn), grads(ring_fn)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=1e-4, rtol=1e-4
        )


@pytest.mark.slow
def test_ring_matches_gather_8way_submesh(rng):
    """8-way mesh, segments spanning BOTH a strict subset of the mesh
    (sl=16 over 4-token shards: rps=4 < world=8 — two independent
    sub-rings) and the full mesh (sl=32: rps=8): ring output and grads
    match the all-gather path and the single-device op."""
    from jax.sharding import Mesh

    from gigapath_tpu.ops.pallas_dilated import PipelineFlags

    mesh = Mesh(np.array(jax.devices()[:8]), ("seq",))
    q, k, v = _qkv3(rng, 1, 32, 4, 8)
    sls, drs = [4, 16, 32], [1, 2, 4]

    ref = dilated_attention(q, k, v, sls, drs)
    gather_fn = _seq_parallel_fn(mesh, 8, sls, drs, PipelineFlags())
    ring_fn = _seq_parallel_fn(
        mesh, 8, sls, drs, PipelineFlags(ring_attn=True)
    )
    out_r = ring_fn(q, k, v)
    np.testing.assert_allclose(
        np.asarray(out_r), np.asarray(gather_fn(q, k, v)), atol=1e-5
    )
    np.testing.assert_allclose(np.asarray(out_r), np.asarray(ref), atol=1e-5)

    def grads(fn):
        def loss(q, k, v):
            return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for a, b in zip(grads(gather_fn), grads(ring_fn)):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=1e-4, rtol=1e-4
        )


def _ragged_seq_parallel_fn(mesh, ndev, sls, drs, flags):
    """shard_map'd dilated_attention deriving per-shard valid counts from
    the SHARDED pad mask — what DilatedAttention._attend does."""
    from jax.sharding import PartitionSpec as P

    from jax import shard_map

    def local(q, k, v, mask):
        vls = (~mask).sum(axis=-1).astype(jnp.int32)
        return dilated_attention(
            q, k, v, sls, drs, seq_axis_name="seq", seq_axis_size=ndev,
            valid_len=vls, flags=flags,
        )

    return shard_map(
        local, mesh=mesh, in_specs=(P(None, "seq"),) * 4,
        out_specs=P(None, "seq"), check_vma=False,
    )


def test_ring_ragged_mask_matches_single_device(rng):
    """Ragged key_padding_mask under the ring: per-ORIGIN-rank valid
    counts (from the hoisted per-call counts gather) mask each resident
    chunk, matching the single-device op at valid positions. Also pins
    the hoist itself: the ragged ring trace carries exactly ONE
    all_gather (the counts — shared by BOTH gathered branches) and the
    gather path's K/V all_gathers are gone."""
    from jax.sharding import Mesh

    from gigapath_tpu.obs import jaxpr_fingerprint
    from gigapath_tpu.ops.pallas_dilated import PipelineFlags

    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    B, N, valid = 1, 32, 25
    q, k, v = _qkv3(rng, B, N, 4, 8)
    sls, drs = [8, 32, 32], [1, 2, 4]  # TWO gathered branches share the hoist
    pad = jnp.arange(N)[None, :] >= valid
    vmask = (~pad).astype(np.float32)[:, :, None, None]

    ref = dilated_attention(
        q, k, v, sls, drs, valid_len=jnp.full((B,), valid, jnp.int32)
    )
    ring_fn = _ragged_seq_parallel_fn(
        mesh, 2, sls, drs, PipelineFlags(ring_attn=True)
    )
    out_r = ring_fn(q, k, v, pad)
    np.testing.assert_allclose(
        np.asarray(out_r) * np.asarray(vmask),
        np.asarray(ref) * np.asarray(vmask), atol=1e-5,
    )

    gather_fn = _ragged_seq_parallel_fn(mesh, 2, sls, drs, PipelineFlags())
    fp_ring = jaxpr_fingerprint(
        lambda q, k, v: ring_fn(q, k, v, pad), q, k, v
    )["primitives"]
    fp_gather = jaxpr_fingerprint(
        lambda q, k, v: gather_fn(q, k, v, pad), q, k, v
    )["primitives"]
    assert fp_ring["all_gather"] == 1, fp_ring  # the hoisted counts only
    assert fp_ring["ppermute"] == 4, fp_ring  # 2 branches x (k, v) x (rps-1)
    assert fp_gather["all_gather"] == 5, fp_gather  # counts + 2 x (k, v)
    assert fp_gather["ppermute"] == 0, fp_gather


@pytest.mark.slow
def test_ring_ragged_grads_match_single_device(rng):
    """Slow sibling: gradients through the ragged ring (custom VJP with
    per-origin-rank chunk masking) match the single-device op 1e-4."""
    from jax.sharding import Mesh

    from gigapath_tpu.ops.pallas_dilated import PipelineFlags

    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    B, N, valid = 1, 32, 25
    q, k, v = _qkv3(rng, B, N, 4, 8)
    sls, drs = [8, 32], [1, 2]
    pad = jnp.arange(N)[None, :] >= valid
    vmask = (~pad).astype(jnp.float32)[:, :, None, None]
    vl_full = jnp.full((B,), valid, jnp.int32)
    ring_fn = _ragged_seq_parallel_fn(
        mesh, 2, sls, drs, PipelineFlags(ring_attn=True)
    )

    def single_loss(q, k, v):
        o = dilated_attention(q, k, v, sls, drs, valid_len=vl_full)
        return ((o.astype(jnp.float32) * vmask) ** 2).sum()

    def ring_loss(q, k, v):
        return ((ring_fn(q, k, v, pad).astype(jnp.float32) * vmask) ** 2).sum()

    g_single = jax.grad(single_loss, argnums=(0, 1, 2))(q, k, v)
    g_ring = jax.grad(ring_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_single, g_ring):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), atol=1e-4, rtol=1e-4
        )


def test_ring_jaxpr_no_kv_all_gather(rng):
    """Acceptance fingerprint (trace-only, 8-way): the non-ragged ring
    program contains ZERO all_gather — K/V move exclusively by ppermute,
    one rotation per non-resident chunk per array, sub-ring-sized for the
    subset segment — while the gather path still all-gathers K and V per
    gathered branch. Grad traces: the ring VJP adds the reverse ring's
    permutes, still zero all_gather."""
    from jax.sharding import Mesh

    from gigapath_tpu.obs import jaxpr_fingerprint
    from gigapath_tpu.ops.pallas_dilated import PipelineFlags

    mesh = Mesh(np.array(jax.devices()[:8]), ("seq",))
    q, k, v = _qkv3(rng, 1, 32, 4, 8)
    sls, drs = [4, 16, 32], [1, 2, 4]  # rps 4 (sub-mesh) and 8 (full)

    def fp(flags, grad=False):
        fn = _seq_parallel_fn(mesh, 8, sls, drs, flags)

        def loss(q, k, v):
            return (fn(q, k, v).astype(jnp.float32) ** 2).sum()

        return jaxpr_fingerprint(
            jax.grad(loss, argnums=(0, 1, 2)) if grad else fn, q, k, v
        )["primitives"]

    ring = fp(PipelineFlags(ring_attn=True))
    gather = fp(PipelineFlags())
    assert ring["all_gather"] == 0, ring
    # (rps-1) x (k, v) per gathered branch: (4-1)*2 + (8-1)*2
    assert ring["ppermute"] == 20, ring
    assert gather["all_gather"] == 4, gather  # 2 branches x (k, v)
    assert gather["ppermute"] == 0, gather

    ring_g = fp(PipelineFlags(ring_attn=True), grad=True)
    assert ring_g["all_gather"] == 0, ring_g
    assert ring_g["ppermute"] > ring["ppermute"], ring_g


def test_ring_env_flag_snapshot_routes(rng, monkeypatch):
    """GIGAPATH_RING_ATTN rides the PipelineFlags snapshot into the ring
    dispatch (trace-only: the spy fires at trace time, no mesh compile)."""
    from jax.sharding import Mesh

    from gigapath_tpu.ops import dilated_attention as da
    from gigapath_tpu.ops.pallas_dilated import PipelineFlags

    calls = []
    real = da._ring_attention

    def spy(qs, ks, vs, counts, *static):
        calls.append(static)
        return real(qs, ks, vs, counts, *static)

    monkeypatch.setattr(da, "_ring_attention", spy)
    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    q, k, v = _qkv3(rng, 1, 16, 4, 8)
    fn = _seq_parallel_fn(mesh, 2, [16], [2], None)  # env-snapshot path

    monkeypatch.setenv("GIGAPATH_RING_ATTN", "1")
    jax.make_jaxpr(fn)(q, k, v)
    assert calls, "flagged trace must route through the ring op"

    calls.clear()
    monkeypatch.setenv("GIGAPATH_RING_ATTN", "0")
    jax.make_jaxpr(fn)(q, k, v)
    assert not calls, "unflagged trace must keep the all-gather path"


def test_ring_flag_keys_do_not_alias(rng):
    """Zero-retrace contract: ring on/off are DISTINCT PipelineFlags
    static keys — two jit cache entries, no silent aliasing of a trace
    made under the other flag value."""
    import functools

    from jax.sharding import Mesh

    from gigapath_tpu.ops.pallas_dilated import PipelineFlags

    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    q, k, v = _qkv3(rng, 1, 8, 2, 4)
    sls, drs = [8], [1]  # one gathered branch, the tiniest ring

    @functools.partial(jax.jit, static_argnums=(3,))
    def f(q, k, v, flags):
        return _seq_parallel_fn(mesh, 2, sls, drs, flags)(q, k, v)

    a = f(q, k, v, PipelineFlags(ring_attn=True))
    b = f(q, k, v, PipelineFlags())
    assert f._cache_size() == 2, (
        "ring on/off must trace under distinct cache keys"
    )
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def test_ring_causal_falls_back_to_gather(rng):
    """A causal gathered branch under the ring flag silently (one
    warning) keeps the all-gather path and stays correct vs the
    single-device causal op."""
    from jax.sharding import Mesh, PartitionSpec as P

    from gigapath_tpu.ops.pallas_dilated import PipelineFlags

    from jax import shard_map

    mesh = Mesh(np.array(jax.devices()[:2]), ("seq",))
    q, k, v = _qkv3(rng, 1, 16, 4, 8)
    sls, drs = [16], [2]

    ref = dilated_attention(q, k, v, sls, drs, is_causal=True)
    fn = shard_map(
        lambda q, k, v: dilated_attention(
            q, k, v, sls, drs, is_causal=True, seq_axis_name="seq",
            seq_axis_size=2, flags=PipelineFlags(ring_attn=True),
        ),
        mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False,
    )
    np.testing.assert_allclose(
        np.asarray(fn(q, k, v)), np.asarray(ref), atol=1e-5
    )


def test_combine_partials_matches_joint_softmax(rng):
    """The stored-LSE merge primitive: attending two disjoint key sets
    separately and combining == attending their concatenation."""
    from gigapath_tpu.ops.flash_attention import (
        combine_partials,
        partial_attention,
    )

    B, Lq, Lk, H, D = 2, 8, 12, 3, 4
    q = jnp.asarray(rng.normal(size=(B, Lq, H, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, 2 * Lk, H, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, 2 * Lk, H, D)), jnp.float32)
    o_full, l_full = attention_with_lse(q, k, v)
    o_a, l_a = partial_attention(q, k[:, :Lk], v[:, :Lk])
    o_b, l_b = partial_attention(q, k[:, Lk:], v[:, Lk:])
    o_c, l_c = combine_partials(o_a.astype(jnp.float32), l_a, o_b, l_b)
    np.testing.assert_allclose(np.asarray(o_c), np.asarray(o_full), atol=1e-5)
    np.testing.assert_allclose(np.asarray(l_c), np.asarray(l_full), atol=1e-5)


# ---------------------------------------------------------------------------
# the dispatch switches: an explicit flags= argument, else ONE read of the
# environment per public call (ops/pallas_dilated.snapshot_flags)
# ---------------------------------------------------------------------------

from gigapath_tpu.ops.pallas_dilated import (  # noqa: E402
    FLAG_ENV,
    PipelineFlags,
    snapshot_flags,
)

_SEGS, _RATIOS = [16, 32], [1, 2]


@pytest.fixture
def no_switches(monkeypatch):
    for name in FLAG_ENV.values():
        monkeypatch.delenv(name, raising=False)


@pytest.fixture
def snapshots(monkeypatch):
    """The calls of ``snapshot_flags`` made while the fixture is live."""
    import gigapath_tpu.ops.pallas_dilated as pd

    calls = []
    real = pd.snapshot_flags
    monkeypatch.setattr(pd, "snapshot_flags", lambda: calls.append(1) or real())
    return calls


def test_no_environment_gives_the_default_carrier(no_switches):
    assert snapshot_flags() == PipelineFlags()


@pytest.mark.parametrize("field", list(FLAG_ENV))
def test_env_twin_sets_its_field_and_no_other(no_switches, monkeypatch, field):
    default = PipelineFlags()
    for off in ("", "0"):
        monkeypatch.setenv(FLAG_ENV[field], off)
        assert snapshot_flags() == default, off
    raw, value = ("256", 256) if "block" in field else ("1", True)
    monkeypatch.setenv(FLAG_ENV[field], raw)
    assert snapshot_flags() == default._replace(**{field: value})


def test_explicit_flags_pin_dispatch(no_switches, monkeypatch, rng):
    """An explicit ``flags=`` wins over the environment: the trace it gives
    is the one the same carrier gives with no environment at all."""
    from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

    q = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)

    def trace(flags):
        return str(jax.make_jaxpr(lambda a: dilated_attention_fused(
            a, a, a, _SEGS, _RATIOS, interpret=True, flags=flags))(q))

    pinned = trace(PipelineFlags())
    from_env_off = trace(None)
    monkeypatch.setenv(FLAG_ENV["streaming_fusion"], "1")
    assert trace(PipelineFlags()) == pinned
    assert trace(None) != from_env_off  # the environment does reach flags=None


def test_resolution_determinism_zero_retraces(no_switches, rng):
    """Same environment -> equal carriers -> one jit cache entry across a
    loop that snapshots once per call."""
    import functools

    from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

    q = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)

    @functools.partial(jax.jit, static_argnums=(1,))
    def step(a, flags):
        return dilated_attention_fused(
            a, a, a, _SEGS, _RATIOS, interpret=True, flags=flags)

    for _ in range(2):
        step(q, snapshot_flags()).block_until_ready()
    assert step._cache_size() == 1


def _call_dilated_attention(q, flags):
    return dilated_attention(q, q, q, _SEGS, _RATIOS, flags=flags)


def _call_fused(q, flags):
    from gigapath_tpu.ops.dilated_attention import dilated_attention_fused

    return dilated_attention_fused(
        q, q, q, _SEGS, _RATIOS, interpret=True, flags=flags)


def _call_branch(q, flags):
    from gigapath_tpu.ops.pallas_dilated import dilated_branch_attention

    B, L, H, Dh = q.shape
    x = q.reshape(B, L, H * Dh)
    return dilated_branch_attention(x, x, x, 32, 2, H, interpret=True, flags=flags)


def _call_stream_fused(q, flags):
    from gigapath_tpu.ops.pallas_dilated import (
        dilated_attention_stream_fused,
        plan_stream_fusion,
    )

    B, L, H, Dh = q.shape
    x = q.reshape(B, L, H * Dh)
    plan = plan_stream_fusion(L, H * Dh, H, _SEGS, _RATIOS, interpret=True,
                              itemsize=4)
    return dilated_attention_stream_fused(
        x, x, x, _SEGS, _RATIOS, H, plan, flags=flags)


@pytest.mark.parametrize("call", [
    _call_dilated_attention, _call_fused, _call_branch, _call_stream_fused,
], ids=["dilated_attention", "dilated_attention_fused",
        "dilated_branch_attention", "dilated_attention_stream_fused"])
def test_one_snapshot_per_public_call(no_switches, monkeypatch, snapshots, call):
    """``flags=None`` reads the environment exactly once, however many
    branches and inner public ops the call goes through; an explicit
    ``flags=`` never reads it. The device gate answers "TPU" (interpret mode)
    so that ``dilated_attention`` walks dispatcher -> fused -> branches."""
    import gigapath_tpu.ops.flash_attention as fa
    from jax.experimental.pallas import tpu as pltpu

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    q = jax.ShapeDtypeStruct((1, 64, 4, 8), jnp.float32)
    with pltpu.force_tpu_interpret_mode():
        jax.eval_shape(lambda a: call(a, None), q)
        assert len(snapshots) == 1
        jax.eval_shape(lambda a: call(a, PipelineFlags()), q)
        assert len(snapshots) == 1
