"""Quantized tile-encoder subsystem (ROADMAP item 3).

- :mod:`gigapath_tpu.quant.qtensor` — quantized-weight containers and
  the ONE sanctioned quantize/dequantize helper set (int8 / fp8-e4m3
  per-channel, f32 dequant contract; gigalint GL016 keeps every other
  low-precision cast out of library code);
- :mod:`gigapath_tpu.quant.qmatmul` — quantized matmul (jnp reference
  tier + Pallas tier) and the ``QuantDense`` flax twin of ``nn.Dense``;
- :mod:`gigapath_tpu.quant.qflash` — int8-logits flash attention (the
  '+attn' rider), same ``(out, lse)`` contract as every attention tier;
- :mod:`gigapath_tpu.quant.convert` — timm/flax checkpoint ->
  calibrated quantized artifact with the resilient-checkpoint manifest
  discipline;
- :mod:`gigapath_tpu.quant.parity` — the drift-vs-oracle harness behind
  ``scripts/ab_tile.py``'s ``adopt_quant_tile`` decision table.

Routing: ``GIGAPATH_QUANT_TILE`` (read once, host side, by
``models/tile_encoder.create_tile_encoder``) selects the tier inside
``models/tile_encoder.py``'s ``ViTAttention``/``SwiGLUPacked``/``Mlp``;
the f32 path stays the fallback and parity oracle.
"""

from gigapath_tpu.quant.qtensor import (  # noqa: F401
    QFP8,
    QINT8,
    QUANT_MODES,
    QTensor,
    base_mode,
    bf16_round_trip,
    dequantize,
    normalize_mode,
    quant_attn,
    quantize_per_channel,
)
