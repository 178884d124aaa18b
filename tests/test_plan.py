"""Geometry-keyed ExecutionPlan dispatch (gigapath_tpu/plan/).

The contracts this file pins (ISSUE acceptance):

- registry round-trip + corrupt-refusal (manifest-discipline file);
- flag-vs-plan precedence: env flags win where PRESENT (including an
  explicit =0 off), the blessed plan fills the rest, defaults last;
- resolution determinism: same shapes -> same resolved plan -> ONE jit
  cache entry across a plan-routed batch loop (zero unexpected
  retraces);
- golden-ledger parity: with an empty registry and no env flags, the
  plan path traces the byte-identical program flags-only dispatch does;
- a blessed plan changes dispatch with zero env flags set (distinct
  jit key + distinct ledger fingerprint) — the in-process twin of
  ``scripts/autotune.py --selftest``, which runs end to end here too;
- the serving AOT artifact identity folds the RESOLVED plan signature,
  so a registry edit can never load a stale-plan executable;
- the tile-encoder factory's quant tier resolves through the seam.
"""

import functools
import json
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from gigapath_tpu.ops.dilated_attention import dilated_attention_fused
from gigapath_tpu.ops.pallas_dilated import (
    FLAG_ENV,
    PipelineFlags,
    snapshot_flags,
)
from gigapath_tpu.plan import (
    CorruptPlanRegistry,
    ExecutionPlan,
    apply_plan,
    bless_plan,
    geometry_key,
    load_registry,
    new_registry,
    plan_stats,
    registry_path,
    reset_plan_state,
    resolve_plan,
    save_registry,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SEGS, RATIOS = [16, 32], [1, 2]


@pytest.fixture
def qkv():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(1, 64, 4, 8)), jnp.float32)
    return q, q, q


@pytest.fixture
def clean_env(monkeypatch, tmp_path):
    """Zero kernel env flags + a private registry path, plan cache
    reset on both sides (tests must never see each other's registry)."""
    for name in list(FLAG_ENV.values()) + ["GIGAPATH_PLAN"]:
        monkeypatch.delenv(name, raising=False)
    registry = str(tmp_path / "PLAN_REGISTRY.json")
    monkeypatch.setenv("GIGAPATH_PLAN_REGISTRY", registry)
    reset_plan_state()
    yield registry
    reset_plan_state()


# ---------------------------------------------------------------------------
# registry file
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_round_trip(self, clean_env):
        plan = ExecutionPlan(
            fusion="stream",
            branches=((16, 1, "", 256), (32, 2, "pipelined", 512)),
            pipe_block_k=512,
        )
        doc = new_registry()
        doc["entries"]["k|sig"] = plan.as_dict()
        save_registry(doc, clean_env)
        again = load_registry(clean_env)
        assert ExecutionPlan.from_dict(again["entries"]["k|sig"]) == plan

    def test_missing_file_is_empty(self, clean_env):
        assert load_registry(clean_env)["entries"] == {}

    def test_corrupt_refusal(self, clean_env):
        save_registry(new_registry(), clean_env)
        with open(clean_env, "a", encoding="utf-8") as fh:
            fh.write("junk")
        with pytest.raises(CorruptPlanRegistry):
            load_registry(clean_env)

    def test_digest_mismatch_refusal(self, clean_env):
        doc = new_registry()
        doc["entries"]["k"] = {"fusion": "stream"}
        save_registry(doc, clean_env)
        body = json.load(open(clean_env, encoding="utf-8"))
        body["entries"]["k"]["fusion"] = "streaming"  # edit without re-hash
        with open(clean_env, "w", encoding="utf-8") as fh:
            json.dump(body, fh)
        with pytest.raises(CorruptPlanRegistry):
            load_registry(clean_env)

    def test_corrupt_registry_resolves_to_defaults(self, clean_env, qkv):
        q, k, v = qkv
        bless_plan(geometry_key("dilated_fused", qkv),
                   ExecutionPlan(fusion="stream").as_dict(), path=clean_env)
        with open(clean_env, "a", encoding="utf-8") as fh:
            fh.write("rot")
        reset_plan_state()
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            resolved = resolve_plan("dilated_fused", qkv)
        assert resolved == PipelineFlags()

    def test_atomic_save_leaves_no_tmp(self, clean_env):
        save_registry(new_registry(), clean_env)
        parent = os.path.dirname(clean_env)
        assert not [p for p in os.listdir(parent) if p.startswith(".tmp-")]

    def test_env_registry_path_wins(self, clean_env):
        assert registry_path() == os.path.abspath(clean_env)


# ---------------------------------------------------------------------------
# precedence + resolution
# ---------------------------------------------------------------------------

class TestPrecedence:
    def test_empty_registry_resolves_to_snapshot(self, clean_env, qkv):
        assert resolve_plan("dilated_fused", qkv) == snapshot_flags()
        assert resolve_plan("dilated_fused", qkv) == PipelineFlags()

    def test_plan_fills_unset_fields(self, clean_env, qkv):
        key = geometry_key("dilated_fused", qkv)
        bless_plan(key, ExecutionPlan(
            fusion="stream", pipelined_fwd=True, pipe_block_k=256,
        ).as_dict(), path=clean_env)
        reset_plan_state()
        resolved = resolve_plan("dilated_fused", qkv)
        assert resolved.stream_fusion
        assert resolved.pipelined_fwd
        assert resolved.pipe_block_k == 256
        # fields the plan has no opinion on keep their defaults
        assert not resolved.ring_attn and resolved.quant_tile == ""

    def test_present_env_flag_beats_plan(self, clean_env, qkv, monkeypatch):
        key = geometry_key("dilated_fused", qkv)
        bless_plan(key, ExecutionPlan(
            fusion="stream", pipelined_fwd=True,
        ).as_dict(), path=clean_env)
        # an explicit =0 is PRESENT: it pins the field off over the plan
        monkeypatch.setenv("GIGAPATH_STREAM_FUSION", "0")
        monkeypatch.setenv("GIGAPATH_PIPELINED_ATTN", "1")
        reset_plan_state()
        resolved = resolve_plan("dilated_fused", qkv)
        assert not resolved.stream_fusion
        assert resolved.pipelined_fwd

    def test_env_pipelined_strips_branch_variants(self, clean_env, qkv,
                                                  monkeypatch):
        key = geometry_key("dilated_fused", qkv)
        bless_plan(key, ExecutionPlan(
            branches=((16, 1, "serial", 256),),
        ).as_dict(), path=clean_env)
        monkeypatch.setenv("GIGAPATH_PIPELINED_ATTN", "1")
        reset_plan_state()
        resolved = resolve_plan("dilated_fused", qkv)
        # env wins: variant stripped, the blessed block survives
        assert resolved.branch_plans == ((16, 1, "", 256),)

    def test_env_pipelined_bwd_survives_serial_variant(self, clean_env,
                                                       qkv, monkeypatch):
        """A per-branch "serial" variant pins the FORWARD only: an
        explicitly set GIGAPATH_PIPELINED_BWD keeps authority over the
        backward (env presence wins, the precedence contract)."""
        from gigapath_tpu.ops.pallas_dilated import _branch_pipelined

        key = geometry_key("dilated_fused", qkv)
        bless_plan(key, ExecutionPlan(
            branches=((16, 1, "serial", 0),),
        ).as_dict(), path=clean_env)
        monkeypatch.setenv("GIGAPATH_PIPELINED_BWD", "1")
        reset_plan_state()
        resolved = resolve_plan("dilated_fused", qkv)
        assert resolved.pipelined_bwd
        fwd, bwd = _branch_pipelined(resolved, 16, 1)
        assert not fwd and bwd

    def test_explicit_flags_pin_dispatch(self, clean_env, qkv):
        key = geometry_key("dilated_fused", qkv)
        bless_plan(key, ExecutionPlan(fusion="stream").as_dict(),
                   path=clean_env)
        reset_plan_state()
        pinned = PipelineFlags()
        assert resolve_plan("dilated_fused", qkv, pinned) is pinned

    def test_plan_off_disables_lookup(self, clean_env, qkv, monkeypatch):
        key = geometry_key("dilated_fused", qkv)
        bless_plan(key, ExecutionPlan(fusion="stream").as_dict(),
                   path=clean_env)
        monkeypatch.setenv("GIGAPATH_PLAN", "off")
        reset_plan_state()
        assert resolve_plan("dilated_fused", qkv) == PipelineFlags()

    def test_quant_tier_via_plan(self, clean_env, qkv):
        key = geometry_key("dilated_fused", qkv)
        bless_plan(key, ExecutionPlan(quant_tile="int8").as_dict(),
                   path=clean_env)
        reset_plan_state()
        assert resolve_plan("dilated_fused", qkv).quant_tile == "int8"

    def test_unknown_quant_tier_entry_refused_not_raised(self, clean_env,
                                                         qkv):
        """A digest-valid entry with an unknown quant_tile spelling is
        refused at lookup (warn once, default dispatch) — it must never
        raise out of resolve_plan on the hot dispatch path."""
        key = geometry_key("dilated_fused", qkv)
        bless_plan(key, {"quant_tile": "int4"}, path=clean_env)
        reset_plan_state()
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            resolved = resolve_plan("dilated_fused", qkv)
        assert resolved == PipelineFlags()
        assert any("refused" in str(w.message) for w in caught)

    def test_hit_stats(self, clean_env, qkv):
        key = geometry_key("dilated_fused", qkv)
        bless_plan(key, ExecutionPlan(fusion="stream").as_dict(),
                   path=clean_env)
        reset_plan_state()
        resolve_plan("dilated_fused", qkv)        # hit
        resolve_plan("dilated_branch", qkv)       # miss (different name)
        stats = plan_stats()
        assert stats["lookups"] == 2 and stats["hits"] == 1
        assert stats["plan_hit_rate"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# determinism + parity
# ---------------------------------------------------------------------------

def _fused(q, k, v, flags):
    return dilated_attention_fused(
        q, k, v, SEGS, RATIOS, interpret=True, flags=flags,
    )


class TestDispatch:
    def test_resolution_determinism_zero_retraces(self, clean_env, qkv):
        """Same shapes -> same resolved plan -> one jit cache entry
        across a plan-routed batch loop."""
        q, k, v = qkv
        key = geometry_key("loop", qkv)
        bless_plan(key, ExecutionPlan(
            fusion="stream", branches=((16, 1, "", 256), (32, 2, "", 256)),
        ).as_dict(), path=clean_env)
        reset_plan_state()

        @functools.partial(jax.jit, static_argnums=(3,))
        def step(q_, k_, v_, flags):
            return _fused(q_, k_, v_, flags)

        for _ in range(4):
            flags = resolve_plan("loop", qkv)  # once per call, per contract
            step(q, k, v, flags).block_until_ready()
        assert step._cache_size() == 1

    def test_golden_parity_plan_on_vs_flags_only(self, clean_env, qkv):
        """Empty registry + no env flags: the plan path resolves to the
        very same PipelineFlags and traces a program whose ledger
        fingerprint is identical to explicit flags-only dispatch (jaxpr
        str equality is spoiled only by closure object reprs inside
        pallas_call params — the eqn histogram is the golden ledger's
        own equality notion)."""
        from gigapath_tpu.obs.ledger import jaxpr_fingerprint

        q, k, v = qkv
        assert resolve_plan("dilated_fused", qkv) == PipelineFlags()

        def plan_routed(q_, k_, v_):
            return dilated_attention_fused(
                q_, k_, v_, SEGS, RATIOS, interpret=True,  # flags=None
            )

        def flags_only(q_, k_, v_):
            return _fused(q_, k_, v_, PipelineFlags())

        assert jaxpr_fingerprint(plan_routed, q, k, v) == \
            jaxpr_fingerprint(flags_only, q, k, v)

    def test_blessed_plan_changes_dispatch_without_env(self, clean_env, qkv):
        """The acceptance demonstration, in process: distinct jit cache
        entry + distinct ledger fingerprint, zero env flags set."""
        from gigapath_tpu.obs.ledger import jaxpr_fingerprint

        q, k, v = qkv
        key = geometry_key("dilated_fused", qkv)
        bless_plan(key, ExecutionPlan(fusion="stream").as_dict(),
                   path=clean_env)
        reset_plan_state()
        resolved = resolve_plan("dilated_fused", qkv)
        assert resolved != PipelineFlags()

        @functools.partial(jax.jit, static_argnums=(3,))
        def step(q_, k_, v_, flags):
            return _fused(q_, k_, v_, flags)

        out_def = step(q, k, v, PipelineFlags())
        out_plan = step(q, k, v, resolved)
        assert step._cache_size() == 2  # the distinct jit key
        fp_def = jaxpr_fingerprint(
            lambda a, b, c: _fused(a, b, c, PipelineFlags()), q, k, v)
        fp_plan = jaxpr_fingerprint(
            lambda a, b, c: _fused(a, b, c, resolved), q, k, v)
        assert fp_def != fp_plan  # the distinct ledger fingerprint
        np.testing.assert_allclose(
            np.asarray(out_def), np.asarray(out_plan), atol=2e-5,
        )

    def test_block_override_parity_fwd_and_grad(self, clean_env, qkv):
        """A blessed per-branch block changes the kernel grid, never the
        math — forward and gradients stay parity with the default."""
        q, k, v = qkv
        flags = apply_plan(ExecutionPlan(
            branches=((16, 1, "", 256), (32, 2, "", 256)),
        ), PipelineFlags())

        def loss(flags):
            def f(a, b, c):
                return (_fused(a, b, c, flags).astype(jnp.float32) ** 2).sum()

            return f

        np.testing.assert_allclose(
            np.asarray(loss(PipelineFlags())(q, k, v)),
            np.asarray(loss(flags)(q, k, v)), rtol=1e-5,
        )
        g_def = jax.grad(loss(PipelineFlags()))(q, k, v)
        g_plan = jax.grad(loss(flags))(q, k, v)
        np.testing.assert_allclose(
            np.asarray(g_def), np.asarray(g_plan), atol=1e-4,
        )


# ---------------------------------------------------------------------------
# dispatch-site satellites: serve AOT identity, tile-encoder quant tier
# ---------------------------------------------------------------------------

class TestServeArtifactIdentity:
    def test_registry_edit_changes_bucket_fingerprints(self, clean_env,
                                                       tmp_path):
        from gigapath_tpu.serve.aot import AotExecutableCache

        def forward(p, embeds, coords, pad_mask):
            return embeds.sum(axis=(1, 2))

        cache = AotExecutableCache(
            forward, {}, feature_dim=16,
            artifact_dir=str(tmp_path / "artifacts"), name="serve.forward",
        )
        before = cache.artifact_path(2, 64)
        other_before = cache.artifact_path(2, 128)
        # bless a plan under an INNER dispatch key (what production
        # blessing actually writes: the model's own dilated_attention
        # geometry, which the compiled forward resolves during its
        # trace — not the bucket-level serve key)
        bless_plan("dilated_attention|float32[1,64,4,8]",
                   ExecutionPlan(fusion="stream").as_dict(), path=clean_env)
        reset_plan_state()
        # EVERY bucket re-fingerprints: no bucket-level check can know
        # which inner keys a trace resolved, so the whole registry
        # state participates — over-invalidation (a recompile), never
        # staleness (wrong dispatch)
        assert cache.artifact_path(2, 64) != before
        assert cache.artifact_path(2, 128) != other_before

    def test_off_missing_and_empty_registry_share_identity(self, clean_env,
                                                           tmp_path,
                                                           monkeypatch):
        """Plan off / missing / empty registry all resolve to the same
        (default) dispatch, so warm restarts across those states still
        load their artifacts."""
        from gigapath_tpu.plan import plan_registry_signature

        missing = plan_registry_signature()
        save_registry(new_registry(), clean_env)
        reset_plan_state()
        empty = plan_registry_signature()
        monkeypatch.setenv("GIGAPATH_PLAN", "off")
        reset_plan_state()
        off = plan_registry_signature()
        assert missing == empty == off == "plan-none"


class TestTileEncoderPlanRouting:
    def test_quant_tier_resolves_through_plan(self, clean_env):
        from gigapath_tpu.models.tile_encoder import create_tile_encoder

        shape = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
        key = geometry_key("tile_encoder.vit_tile_enc_test", (shape,))
        bless_plan(key, ExecutionPlan(quant_tile="int8").as_dict(),
                   path=clean_env)
        reset_plan_state()
        model, _ = create_tile_encoder("", "vit_tile_enc_test")
        assert model.quant == "int8"

    def test_explicit_kwarg_pins_tier(self, clean_env):
        from gigapath_tpu.models.tile_encoder import create_tile_encoder

        shape = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
        key = geometry_key("tile_encoder.vit_tile_enc_test", (shape,))
        bless_plan(key, ExecutionPlan(quant_tile="int8").as_dict(),
                   path=clean_env)
        reset_plan_state()
        model, _ = create_tile_encoder("", "vit_tile_enc_test", quant="")
        assert model.quant == ""

    def test_no_plan_no_env_is_f32_oracle(self, clean_env):
        from gigapath_tpu.models.tile_encoder import create_tile_encoder

        model, _ = create_tile_encoder("", "vit_tile_enc_test")
        assert model.quant == "" and not model.quant_pallas


# ---------------------------------------------------------------------------
# the autotuner, end to end
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_autotune_selftest_subprocess():
    """The seeded-sweep acceptance: ``scripts/autotune.py --selftest``
    (sweep -> bless -> zero-env dispatch change -> precedence ->
    corrupt refusal). Slow tier: it compiles several interpret-mode
    candidates; the fast siblings above cover each contract in
    process."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, "scripts", "autotune.py"),
         "--selftest"],
        capture_output=True, text=True, cwd=REPO_ROOT,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "autotune selftest OK" in proc.stdout


def test_autotune_sweep_emits_decision_table(tmp_path, monkeypatch):
    """Fast sibling: one tiny CPU sweep emits the adopt_plan decision
    table with the always-on gates evaluated and walltime null (CPU
    rows never pass the walltime gate, the ab_dilated discipline)."""
    for name in list(FLAG_ENV.values()) + ["GIGAPATH_PLAN"]:
        monkeypatch.delenv(name, raising=False)
    registry = str(tmp_path / "reg.json")
    monkeypatch.setenv("GIGAPATH_PLAN_REGISTRY", registry)
    reset_plan_state()
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    import autotune

    out = str(tmp_path / "AUTOTUNE.json")
    rc = autotune.main([
        "--segments", "16,32", "--ratios", "1,2", "--n", "64",
        "--heads", "4", "--head-dim", "8", "--blocks", "",
        "--registry", registry, "--json", out, "--label", "test",
    ])
    assert rc == 0
    payload = json.load(open(out, encoding="utf-8"))
    assert payload["metric"] == "autotune"
    assert payload["backend"] == "cpu"
    assert payload["best_wall_s"] is None  # walltime gate is chip-only
    assert "default" in payload["rows"]
    assert payload["rows"]["stream"]["gates_ok"] in (True, False)
    assert payload["decision"]["adopt_plan"] in (True, False)
    # CPU + no memory win => nothing blessed without --force-bless
    assert not os.path.exists(registry) or \
        load_registry(registry)["entries"] == {} or \
        payload["decision"]["blessed"]
    reset_plan_state()


class TestFoldPlanFields:
    """The streaming-fold carriers (ISSUE 20): fold_pallas /
    fold_block_q / fold_block_k / fold_branches ride ExecutionPlan
    through the same round-trip, precedence, and bless machinery as the
    dilated-attention fields."""

    def test_fold_round_trip(self, clean_env):
        plan = ExecutionPlan(
            fold_pallas=True, fold_block_q=512, fold_block_k=256,
            fold_branches=((2048, 2, 256, 128), (16384, 1, 0, 512)),
        )
        doc = new_registry()
        doc["entries"]["stream_fold|sig"] = plan.as_dict()
        save_registry(doc, clean_env)
        again = load_registry(clean_env)
        assert ExecutionPlan.from_dict(
            again["entries"]["stream_fold|sig"]
        ) == plan

    def test_fold_plan_fills_flags(self, clean_env, qkv):
        key = geometry_key("stream_fold", qkv)
        bless_plan(key, ExecutionPlan(
            fold_pallas=True, fold_block_q=512,
            fold_branches=((16, 1, 128, 128),),
        ).as_dict(), path=clean_env)
        reset_plan_state()
        resolved = resolve_plan("stream_fold", qkv)
        assert resolved.fold_pallas
        assert resolved.fold_block_q == 512
        assert resolved.fold_branches == ((16, 1, 128, 128),)
        # fields the plan has no opinion on keep their defaults
        assert resolved.fold_block_k is None
        assert not resolved.stream_fusion

    def test_env_fold_flag_beats_plan(self, clean_env, qkv, monkeypatch):
        key = geometry_key("stream_fold", qkv)
        bless_plan(key, ExecutionPlan(
            fold_pallas=True, fold_block_q=512,
            fold_branches=((16, 1, 128, 256),),
        ).as_dict(), path=clean_env)
        # an explicit =0 is PRESENT: it pins fold_pallas off over the
        # plan; the present block-q env strips the plan's per-branch
        # bq to 0 (auto) while the bk column survives untouched
        monkeypatch.setenv(FLAG_ENV["fold_pallas"], "0")
        monkeypatch.setenv(FLAG_ENV["fold_block_q"], "64")
        reset_plan_state()
        resolved = resolve_plan("stream_fold", qkv)
        assert not resolved.fold_pallas
        assert resolved.fold_block_q == 64
        assert resolved.fold_branches == ((16, 1, 0, 256),)


def test_autotune_fold_sweep_emits_decision_table(tmp_path, monkeypatch):
    """The fold-surface sibling of the dilated sweep test: one tiny CPU
    sweep over --surface fold emits candidates ranked with mask-eqn
    A/B (jnp default > 0, Pallas fold == 0) and the adopt decision."""
    for name in list(FLAG_ENV.values()) + ["GIGAPATH_PLAN"]:
        monkeypatch.delenv(name, raising=False)
    registry = str(tmp_path / "reg.json")
    monkeypatch.setenv("GIGAPATH_PLAN_REGISTRY", registry)
    reset_plan_state()
    sys.path.insert(0, os.path.join(REPO_ROOT, "scripts"))
    import autotune

    out = str(tmp_path / "AUTOTUNE_FOLD.json")
    rc = autotune.main([
        "--surface", "fold", "--segments", "16,32", "--ratios", "1,2",
        "--chunk", "64", "--valid", "256", "--heads", "4",
        "--head-dim", "8", "--blocks", "128",
        # interpret-mode emulation buffers dominate peak bytes at this
        # toy geometry (see the autotune selftest): relax the byte gate
        # so the decision machinery, not perf, is what's under test
        "--gate-rel-tol", "10.0", "--eqn-tol", "64",
        "--registry", registry, "--json", out, "--label", "test",
    ])
    assert rc == 0
    payload = json.load(open(out, encoding="utf-8"))
    assert payload["metric"] == "fold_autotune"
    assert payload["best_wall_s"] is None  # walltime gate is chip-only
    rows = payload["rows"]
    assert {"default", "fold", "fold_b128"} <= set(rows)
    assert rows["default"]["mask_eqns"] > 0
    assert rows["fold"]["mask_eqns"] == 0
    assert payload["decision"]["adopt_plan"] in (True, False)
    reset_plan_state()
