"""Pallas streaming-fold tier (ops/pallas_streaming.py) + its dispatch
switches.

The contracts this file pins (ISSUE 20 acceptance):

- interpret-mode parity of the Pallas ``pair_partial`` against the jnp
  oracle (``ops/streaming_prefill.pair_partial_attention``): forward
  1e-5 / grads 1e-4, including ragged ``valid_len`` tails, uneven
  head/ratio splits, and fully-masked pairs (sentinel discipline: both
  tiers' masked-row lse weighs to exactly zero downstream, but the raw
  sentinels differ — ~NEG_INF for the oracle, ~-7e19 for the kernel's
  underflow — so row comparisons gate on coverage);
- out-of-order chunk delivery is BIT-exact vs in-order under the Pallas
  path, including the bf16 fused result (deterministic fold sequence);
- flags on-vs-off produce DISTINCT jit cache keys (flags ride the
  fold executable as a static arg);
- zero env flags -> the snapshot's fold traces the byte-identical
  program the plain jnp path (``flags=None``) traces;
- the streaming session reads the environment ONCE at construction —
  never per chunk or per fold.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gigapath_tpu.models.slide_encoder import LongNetViT
from gigapath_tpu.ops.attention import NEG_INF
from gigapath_tpu.ops.pallas_dilated import (
    FLAG_ENV,
    PipelineFlags,
    snapshot_flags,
)
from gigapath_tpu.ops.pallas_streaming import (
    DEFAULT_FOLD_BLOCK,
    fold_blocks,
    pallas_pair_partial,
)
from gigapath_tpu.ops.streaming_prefill import (
    StreamingPrefillState,
    chunk_bounds,
    fold_pair,
    pair_partial_attention,
    streaming_dilated_attention,
)
PALLAS = PipelineFlags(fold_pallas=True)

# covered-row threshold: a real lse is O(logits) ~ O(10); both tiers'
# fully-masked sentinels sit far below NEG_INF/2 (the same finite check
# StreamingPrefillState.lse_spread uses)
_COVERED = NEG_INF * 0.5


@pytest.fixture(autouse=True)
def _pallas_interpret_mode():
    """The fold's dispatch seam (``flags.fold_pallas``) passes no
    ``interpret`` of its own: on this CPU platform the tests ask for
    interpret mode explicitly, through Pallas' own switch."""
    from jax.experimental.pallas import tpu as pltpu

    with pltpu.force_tpu_interpret_mode():
        yield


@pytest.fixture
def clean_env(monkeypatch):
    """Zero kernel env flags (the dispatch tests must never see a
    user's)."""
    for name in FLAG_ENV.values():
        monkeypatch.delenv(name, raising=False)


def _blk(rng, B, c, H, Dh, dtype=jnp.float32):
    return jnp.asarray(rng.normal(size=(B, c, H, Dh)), dtype)


# one row per mask regime: local/in-segment, offset chunks, uneven
# H % r, ragged valid tail crossing the key chunk, ragged cq != ck,
# and a fully-masked pair (disjoint segments)
PAIR_CASES = [
    # (g, r, q0, k0, cq, ck, valid, H)
    (64, 1, 0, 0, 64, 64, None, 4),
    (128, 2, 64, 0, 64, 64, 100, 4),
    (64, 2, 0, 64, 64, 64, None, 6),
    (128, 4, 128, 0, 48, 64, 150, 4),
    (64, 1, 0, 64, 64, 64, None, 4),
]


class TestPairPartialParity:
    @pytest.mark.parametrize("g,r,q0,k0,cq,ck,valid,H", PAIR_CASES)
    def test_forward_matches_jnp_oracle(self, g, r, q0, k0, cq, ck,
                                        valid, H):
        rng = np.random.default_rng(0)
        q = _blk(rng, 1, cq, H, 8)
        k = _blk(rng, 1, ck, H, 8)
        v = _blk(rng, 1, ck, H, 8)
        o_ref, l_ref = pair_partial_attention(
            q, k, v, jnp.int32(q0), jnp.int32(k0),
            segment_len=g, ratio=r, valid_len=valid,
        )
        o_pl, l_pl = pallas_pair_partial(
            q, k, v, jnp.int32(q0), jnp.int32(k0),
            segment_len=g, ratio=r, valid_len=valid, interpret=True,
        )
        covered = np.asarray(l_ref) > _COVERED
        np.testing.assert_allclose(
            np.asarray(o_pl), np.asarray(o_ref), atol=1e-5, rtol=0,
        )
        np.testing.assert_allclose(
            np.asarray(l_pl)[covered], np.asarray(l_ref)[covered],
            atol=1e-5, rtol=0,
        )
        # uncovered rows: the kernel's sentinel must still weigh to
        # zero in any downstream combine — i.e. sit far below any lse
        assert (np.asarray(l_pl)[~covered] < _COVERED).all()
        # and the oracle's own covered set must agree with the kernel's
        assert ((np.asarray(l_pl) > _COVERED) == covered).all()

    # fwd covers every mask regime; grads re-check the three that
    # exercise distinct VJP paths (local, offset+ragged valid, ragged
    # cq) — each grad case re-traces both tiers, so keep the set lean
    @pytest.mark.parametrize(
        "g,r,q0,k0,cq,ck,valid,H",
        [PAIR_CASES[0], PAIR_CASES[1], PAIR_CASES[3]],
    )
    def test_grads_match_jnp_oracle(self, g, r, q0, k0, cq, ck, valid, H):
        """Grad parity THROUGH the fold step (combine_partials
        differentiates through the pair lse, so the dlse cotangent path
        of the custom VJP is exercised, not just do)."""
        rng = np.random.default_rng(1)
        q = _blk(rng, 1, cq, H, 8)
        k = _blk(rng, 1, ck, H, 8)
        v = _blk(rng, 1, ck, H, 8)
        acc_o = _blk(rng, 1, cq, H, 8) * 0.1
        acc_l = jnp.asarray(
            rng.normal(size=(1, H, cq)), jnp.float32
        )  # a live accumulator: every fold output row is covered

        def loss(flags):
            def f(q_, k_, v_):
                o, l = fold_pair(
                    acc_o, acc_l, q_, k_, v_,
                    jnp.int32(q0), jnp.int32(k0),
                    jnp.int32(valid if valid is not None else q0 + k0 + 512),
                    segment_len=g, ratio=r, flags=flags,
                )
                return (o.astype(jnp.float32) ** 2).sum() + (l ** 2).sum()

            return jax.grad(f, argnums=(0, 1, 2))(q, k, v)

        g_ref = loss(None)
        g_pl = loss(PALLAS)
        for name, a, b in zip("qkv", g_ref, g_pl):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), atol=1e-4, rtol=0,
                err_msg=f"d{name}",
            )

    def test_streaming_fused_parity_with_ragged_tail(self):
        """End-to-end through streaming_dilated_attention: the Pallas
        tier's fused chunk outputs match the jnp path at 1e-5 with a
        ragged valid_len tail masking the final chunk."""
        rng = np.random.default_rng(2)
        L, C, H, Dh = 256, 64, 4, 8
        bounds = chunk_bounds(L, C)
        blocks = [
            tuple(_blk(rng, 1, b - a, H, Dh) for _ in range(3))
            for a, b in bounds
        ]
        qb, kb, vb = (list(t) for t in zip(*blocks))
        kwargs = dict(
            bounds=bounds, segment_lengths=[64, 128],
            dilated_ratios=[1, 2], valid_len=230,
        )
        ref = streaming_dilated_attention(qb, kb, vb, **kwargs)
        got = streaming_dilated_attention(qb, kb, vb, flags=PALLAS,
                                          **kwargs)
        for i, (a, b) in enumerate(zip(ref, got)):
            np.testing.assert_allclose(
                np.asarray(b), np.asarray(a), atol=1e-5, rtol=0,
                err_msg=f"chunk {i}",
            )


class TestDeterminism:
    def _run(self, order, blocks, bounds, dtype):
        """Deliver chunks in ``order`` through a frontier buffer (the
        session's OOO discipline) into a Pallas-flagged fold state."""
        state = StreamingPrefillState(
            bounds, [64, 128], [1, 2], valid_len=230, flags=PALLAS,
        )
        held, nxt = {}, 0
        for i in order:
            held[i] = blocks[i]
            while nxt in held:
                state.ingest(nxt, *held.pop(nxt))
                nxt += 1
        assert nxt == len(bounds)
        return [np.asarray(o) for o in state.finalize()]

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_out_of_order_arrival_is_bit_exact(self, dtype):
        """The fused result — including bf16 — is a pure function of
        the slide geometry, not delivery order: the frontier buffer
        replays the identical fold sequence, and the Pallas kernels are
        deterministic, so equality is BIT-exact, not approximate."""
        rng = np.random.default_rng(3)
        bounds = chunk_bounds(256, 64)
        blocks = [
            tuple(_blk(rng, 1, b - a, 4, 8, dtype) for _ in range(3))
            for a, b in bounds
        ]
        base = self._run(range(len(bounds)), blocks, bounds, dtype)
        ooo = self._run([2, 0, 3, 1], blocks, bounds, dtype)
        # finalize fuses in fp32 regardless of input dtype (the fold
        # accumulator discipline) — the bf16 case pins that bf16 INPUT
        # streams still land on one bit pattern per geometry
        assert base[0].dtype == np.float32
        for i, (a, b) in enumerate(zip(base, ooo)):
            assert np.array_equal(a, b), f"chunk {i} not bit-exact"


class TestDispatchSwitches:
    def test_fold_blocks_precedence(self):
        # the carrier's field where set, else the module default
        flags = PipelineFlags(fold_pallas=True, fold_block_q=512)
        assert fold_blocks(flags) == (512, DEFAULT_FOLD_BLOCK)
        assert fold_blocks(flags._replace(fold_block_k=384)) == (512, 384)
        assert fold_blocks(PipelineFlags()) == (
            DEFAULT_FOLD_BLOCK, DEFAULT_FOLD_BLOCK,
        )

    def test_env_twin_sets_the_fold_block(self, clean_env, monkeypatch):
        # an explicit =0 keeps the tier off; the block twin is honoured
        monkeypatch.setenv(FLAG_ENV["fold_pallas"], "0")
        monkeypatch.setenv(FLAG_ENV["fold_block_q"], "64")
        snap = snapshot_flags()
        assert not snap.fold_pallas
        assert fold_blocks(snap) == (64, DEFAULT_FOLD_BLOCK)

    def test_flag_on_vs_off_distinct_jit_keys(self, clean_env):
        rng = np.random.default_rng(4)
        q = _blk(rng, 1, 64, 4, 8)
        acc_o = jnp.zeros((1, 64, 4, 8), jnp.float32)
        acc_l = jnp.full((1, 4, 64), NEG_INF, jnp.float32)
        jfold = jax.jit(
            fold_pair, static_argnames=("segment_len", "ratio", "flags")
        )
        args = (acc_o, acc_l, q, q, q,
                jnp.int32(0), jnp.int32(0), jnp.int32(64))
        jfold(*args, segment_len=64, ratio=1, flags=None)
        jfold(*args, segment_len=64, ratio=1, flags=None)
        base = jfold._cache_size()
        # an explicit flags= re-keys
        jfold(*args, segment_len=64, ratio=1, flags=PALLAS)
        assert jfold._cache_size() > base  # the DISTINCT key
        grown = jfold._cache_size()
        # replays of either static value hit their existing entries
        jfold(*args, segment_len=64, ratio=1, flags=None)
        jfold(*args, segment_len=64, ratio=1, flags=PALLAS)
        assert jfold._cache_size() == grown

    def test_no_environment_traces_the_jnp_fold(self, clean_env):
        """The parity-oracle guarantee: with no env flags, the
        snapshot's dispatch traces the very program the plain jnp fold
        traces — compared as jaxpr text, not numerics."""
        rng = np.random.default_rng(5)
        q = _blk(rng, 1, 64, 4, 8)
        acc_o = jnp.zeros((1, 64, 4, 8), jnp.float32)
        acc_l = jnp.full((1, 4, 64), NEG_INF, jnp.float32)
        snap = snapshot_flags()
        assert snap == PipelineFlags()

        def trace(flags):
            return str(jax.make_jaxpr(
                lambda *a: fold_pair(*a, segment_len=64, ratio=1,
                                     flags=flags)
            )(acc_o, acc_l, q, q, q,
              jnp.int32(0), jnp.int32(0), jnp.int32(64)))

        assert trace(None) == trace(snap)

    @pytest.mark.parametrize("twin,carrier", [
        (None, PipelineFlags()),
        ("fold_block_q", PipelineFlags(fold_block_q=64)),
    ], ids=["no_environment", "fold_block_q_64"])
    def test_session_snapshots_once(self, clean_env, monkeypatch, twin,
                                    carrier):
        """ONE snapshot_flags() per session construction, whatever the
        environment holds — feeding every chunk and finalizing adds
        zero reads."""
        import gigapath_tpu.ops.pallas_dilated as pd

        if twin:
            monkeypatch.setenv(FLAG_ENV[twin], "64")

        rng = np.random.default_rng(6)
        model = LongNetViT(
            in_chans=16, embed_dim=32, depth=1, slide_ngrids=100,
            segment_length=[16, 32], dilated_ratio="[1, 2]",
            dropout=0.0, drop_path_rate=0.0,
        )
        from gigapath_tpu.models.streaming_encoder import (
            StreamingEncoderSession,
        )

        n = 24
        x = jnp.asarray(rng.normal(size=(1, n, 16)), jnp.float32)
        coords = jnp.asarray(
            rng.uniform(0, 100 * 256, (1, n, 2)), jnp.float32
        )
        params = model.init(jax.random.PRNGKey(0), x, coords)["params"]
        reads = []
        real = pd.snapshot_flags
        monkeypatch.setattr(
            pd, "snapshot_flags", lambda: reads.append(1) or real()
        )  # after init: the dense path took its own snapshots there
        session = StreamingEncoderSession(model, params, n, chunk_tiles=8)
        assert len(reads) == 1
        assert session.fold_flags == carrier
        xn, cn = np.asarray(x[0]), np.asarray(coords[0])
        for i, (a, b) in enumerate(session.tile_bounds):
            session.feed(i, xn[a:b], cn[a:b])
        session.finalize()
        assert len(reads) == 1  # still the ONE read
