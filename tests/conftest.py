"""Test configuration: force an 8-device CPU platform before JAX initializes.

The reference has no test suite at all (SURVEY.md §4); here the suite runs on
a virtual 8-device CPU platform (``--xla_force_host_platform_device_count``) so
distributed code paths (mesh sharding, collectives) can be validated without
TPU hardware as they land.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Drivers' mains point JAX's persistent compile cache at a directory inside
# the checkout (gigapath_tpu/utils/compile_cache.py), and tests call those
# mains in-process. The suite neither writes nor reads that cache: compile
# counts pinned by the tests stay what this process compiled.
jax.config.update("jax_enable_compilation_cache", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--runslow",
        action="store_true",
        default=False,
        help="also run tests marked slow (interpret-mode Pallas kernels, "
        "mesh suites, multi-minute compile loops)",
    )


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: multi-minute tests (Pallas interpret mode, 8-device mesh "
        "compiles); skipped by default, enabled with --runslow or RUN_SLOW=1",
    )


# Tests >= ~7 s on the 8-device virtual CPU mesh (measured round 5,
# pytest --durations=50 under load; the full suite was ~30 min). Matched
# by nodeid substring so the tier list lives in ONE place; tests may also
# self-mark with @pytest.mark.slow. Everything here has a faster sibling
# covering the same code path in the default tier.
_SLOW_NODEIDS = (
    "test_dilated_attention.py::TestFusedPhaseMajorPath::test_gradients_match_generic",
    "test_dilated_attention.py::TestFusedPhaseMajorPath::test_traced_valid_len_matches_static",
    "test_dilated_attention.py::TestFusedPhaseMajorPath::test_valid_len_and_causal_match_generic",
    "test_dilated_attention.py::TestFusedPhaseMajorPath::test_matches_oracle",
    "test_dilated_attention.py::TestFusedPhaseMajorPath::test_odd_ratio_falls_back",
    "test_dilated_attention.py::test_seq_parallel_matches_single_device",
    "test_dilated_attention.py::test_seq_parallel_causal_matches_single_device",
    "test_dilated_attention.py::TestBHLDFastPath::test_traced_valid_len_gradients",
    "test_dilated_attention.py::TestBHLDFastPath::test_valid_len_matches_generic",
    "test_dilated_attention.py::TestBHLDFastPath::test_jnp_tier_matches_oracle",
    "test_dilated_attention.py::TestBHLDFastPath::test_pallas_tier_matches_oracle",
    "test_dilated_attention.py::TestBHLDFastPath::test_gradients_match_generic",
    "test_dilated_attention.py::TestBHLDFastPath::test_causal_matches_generic",
    "test_dilated_attention.py::TestBHLDFastPath::test_traced_valid_len_matches_generic",
    "test_dilated_attention.py::TestOffsetDecode::test_stepwise_matches_full",
    "test_dilated_attention.py::TestOffsetDecode::test_chunked_matches_full",
    "test_dilated_attention.py::test_fused_streaming_matches_stacked",
    "test_dilated_attention.py::test_streaming_fusion_matches_stacked",
    "test_dilated_attention.py::test_module_gigapath_schedule",
    "test_dilated_attention.py::test_gradients_flow",
    "test_dilated_attention.py::test_multibranch_matches_oracle",
    "test_dilated_attention.py::test_longnet_decoder_incremental_matches_full",
    # round-8 rebalance (durations re-measured, same >= ~7 s bar):
    # seq-parallel ragged routing has test_seq_parallel_fused_routing_fast;
    # the 8-mesh ring-vs-gather A/B has the single-device ragged ring
    # parity + the golden ring-signal ledger pin; the multiclass stream
    # state chain has the epilogue grad-parity + jaxpr siblings
    "test_dilated_attention.py::test_seq_parallel_ragged_mask_fused_routing",
    "test_dilated_attention.py::test_ring_matches_gather_seq_parallel",
    "test_dilated_attention.py::TestStreamFusionEpilogue::test_multiclass_state_chain",
    "test_finetune_harness.py::test_finetune_main_end_to_end",
    "test_moe.py::TestMoEEncoder::test_train_step_moe_aux_weight",
    "test_moe.py::TestMoEEncoder::test_moe_longnet_encoder_trains_one_step",
    "test_moe.py::TestExpertParallel::test_shard_map_all_to_all_matches_serial",
    "test_moe.py::TestExpertParallel::test_gspmd_expert_sharding_matches_single_device",
    "test_moe.py::TestMOELayer::test_output_is_convex_expert_mix",
    "test_encoder.py::test_longnet_remat_matches_plain",
    "test_encoder.py::test_longnet_from_name_small",
    "test_parallel.py::test_sharded_train_step_matches_single_device",
    "test_slide_encoder.py::test_global_pool_differs_from_cls",
    "test_slide_encoder.py::test_forward_shapes",
    "test_decoder_retnet.py::TestEncoderDecoder::test_moe_layers_use_side_specific_dims",
    "test_decoder_retnet.py::TestBertInit::test_trunc_normal_redraw",
    "test_decoder_retnet.py::TestDecoder::test_moe_decoder_layer",
    "test_decoder_retnet.py::TestDecoder::test_incremental_decode_matches_full",
    "test_train_driver.py::test_rename_and_full_journey",
    "test_pad_masking.py::test_slide_encoder_pad_mask_matches_unpadded",
    "test_pad_masking.py::test_slide_encoder_global_pool_excludes_pads",
    "test_pipeline_drivers.py::TestPipeline::test_tile_encode_slide_encode",
    "test_pipeline_drivers.py::TestPretrain::test_mae_loss_decreases",
    "test_pallas_flash.py::test_kv_len_ragged_masking",
    "test_pallas_flash.py::test_gradients_match_reference",
    "test_pallas_flash.py::test_bwd_impl_asymmetric_blocks_match",
    "test_pallas_flash.py::test_kv_len_masks_large_real_keys",
    "test_pallas_flash.py::test_flat_bwd_resegment_fallback_matches",
    "test_beit3.py::TestBEiT3::test_fused_vision_language",
    "test_beit3.py::TestBEiT3::test_single_modality",
    "test_pad_masking.py::test_classification_head_logits_invariant_to_bucket",
    "test_pad_masking.py::test_dilated_attention_valid_len_matches_unpadded",
    "test_slide_encoder.py::test_torch_checkpoint_roundtrip",
    "test_encoder.py::test_remat_with_dropout_traces",
    "test_pipeline_drivers.py::TestPredict::test_predict_writes_csv",
    "test_pallas_flash.py::test_flat_bwd_fallback_masks_invalid_row_cotangents",
)


def pytest_collection_modifyitems(config, items):
    # same truthiness convention as every other repo flag (env_flag in
    # gigapath_tpu/ops/common.py): ''/'0'/'false'/'no' mean OFF
    run_slow = os.environ.get("RUN_SLOW", "").strip().lower() not in (
        "", "0", "false", "no",
    )
    if config.getoption("--runslow") or run_slow:
        return
    skip = pytest.mark.skip(reason="slow tier: pass --runslow (or RUN_SLOW=1)")
    for item in items:
        # exact match on the de-parametrized nodeid: substring matching
        # would also catch tests whose NAME merely extends a listed name
        base = item.nodeid.split("[")[0]
        if "slow" in item.keywords or any(
            base.endswith(nid) for nid in _SLOW_NODEIDS
        ):
            item.add_marker(skip)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


@pytest.fixture(scope="session")
def serve_tiny_model():
    """The ONE tiny f32 serving model shared by test_serve.py and
    test_serve_obs.py (building it costs ~10 s of flax init — paying it
    once per session instead of once per module keeps tier-1 inside its
    wall budget). f32 (dtype=None) because the serving parity bars are
    float32 statements."""
    from gigapath_tpu.models.classification_head import get_model

    return get_model(
        input_dim=16, latent_dim=32, feat_layer="1", n_classes=2,
        model_arch="gigapath_slide_enc_tiny", dtype=None,
    )
