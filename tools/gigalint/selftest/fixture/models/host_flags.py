"""Negative control for GL017: host-side reads of flags that are NOT
kernel-dispatch switches — the twins of the real
models/tile_encoder.create_tile_encoder (the quant tier) and
models/streaming_encoder.chunked_prefill_default (a driver's choice of
loop). Neither is a field of PipelineFlags, so the rule has no business
with them, exactly like the fixture's quant/qtensor.py (GL016) and
dist/transport.py (GL015) twins."""

import os


def negative_control_factory_reads_quant_tier():
    # a model factory picks its weight tier once, host side
    return os.environ.get("GIGAPATH_QUANT_TILE", "").strip().lower()


def negative_control_driver_reads_chunked_prefill():
    # a driver picks its loop before it runs anything
    return os.environ.get("GIGAPATH_CHUNKED_PREFILL", "") == "1"
