"""User-facing inference pipeline: tile a slide, encode tiles, encode slide.

Parity with reference ``gigapath/pipeline.py``: the same five entry points —
``tile_one_slide`` (L55), ``load_tile_encoder_transforms`` (L106),
``load_tile_slide_encoder`` (L118), ``run_inference_with_tile_encoder``
(L140), ``run_inference_with_slide_encoder`` (L165) — plus the
streaming twin ``run_inference_with_slide_encoder_streaming`` (chunked
prefill: a chunk iterator/channel instead of the dense array; README
"Streaming prefill") — with the same
invariants (dataset.csv non-empty, failed_tiles.csv empty after tiling;
batch-128 bf16 tile encoding; all-layer slide embeddings keyed
``layer_{i}_embed`` + ``last_layer_embed``).

TPU shape: the tile encoder runs as one jitted bf16 forward over fixed
[128, 224, 224, 3] batches (the last partial batch is padded then sliced,
so a slide triggers exactly one compile); transfers are one
``device_put`` per batch. Checkpoints load from local paths (zero-egress
build; HF-hub names fall back to random init with a warning).
"""

from __future__ import annotations

import functools
import os
from pathlib import Path
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gigapath_tpu.data.tile_dataset import TileEncodingDataset
from gigapath_tpu.data.transforms import preprocess_tile
from gigapath_tpu.models import slide_encoder as slide_encoder_lib
from gigapath_tpu.models import tile_encoder as tile_encoder_lib
from gigapath_tpu.obs import console, span
from gigapath_tpu.preprocessing.create_tiles_dataset import process_slide


def tile_one_slide(
    slide_file: str = "",
    save_dir: str = "",
    level: int = 0,
    tile_size: int = 256,
):
    """Tile a single slide to ``save_dir/output/<slide_id>/`` and assert the
    reference's ledger invariants (``pipeline.py:55-103``)."""
    import pandas as pd

    slide_id = os.path.basename(slide_file)
    slide_sample = {"image": slide_file, "slide_id": slide_id, "metadata": {}}

    save_dir = Path(save_dir)
    if save_dir.exists():
        console(f"Warning: Directory {save_dir} already exists. ")
    console(
        f"Processing slide {slide_file} at level {level} with tile size "
        f"{tile_size}. Saving to {save_dir}."
    )
    slide_dir = process_slide(
        slide_sample,
        level=level,
        margin=0,
        tile_size=tile_size,
        foreground_threshold=None,
        occupancy_threshold=0.1,
        output_dir=save_dir / "output",
        thumbnail_dir=save_dir / "thumbnails",
        tile_progress=True,
    )
    dataset_df = pd.read_csv(slide_dir / "dataset.csv")
    assert len(dataset_df) > 0
    failed_df = pd.read_csv(slide_dir / "failed_tiles.csv")
    assert len(failed_df) == 0
    console(
        f"Slide {slide_file} has been tiled. {len(dataset_df)} tiles saved to {slide_dir}."
    )
    return slide_dir


def load_tile_encoder_transforms(crop_size: int = 224):
    """The tile transform (resize-256 bicubic / center-crop-224 / ImageNet
    normalize), as a plain callable on PIL images or uint8 arrays."""
    return lambda img: preprocess_tile(img, crop_size=crop_size)


def load_tile_slide_encoder(
    local_tile_encoder_path: str = "",
    local_slide_encoder_path: str = "",
    global_pool: bool = False,
    *,
    tile_arch: str = "gigapath_tile_enc",
    slide_arch: str = "gigapath_slide_enc12l768d",
) -> Tuple[tuple, tuple]:
    """Load both encoders; returns ``((tile_model, tile_params),
    (slide_model, slide_params))`` (reference ``pipeline.py:118-137``).

    The tile encoder's quant tier is read inside the factory
    (``GIGAPATH_QUANT_TILE``, one host-side read): quant off builds the
    f32/bf16 program, quant on builds the quantized-Dense tier — a
    distinct traced program, so the jit cache can never serve the wrong
    tier."""
    tile_model, tile_params = tile_encoder_lib.create_tile_encoder(
        pretrained=local_tile_encoder_path, model_arch=tile_arch,
        dtype=jnp.bfloat16,
    )
    n_tile = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(tile_params))
    console(f"Tile encoder param # {n_tile}")

    slide_model, slide_params = slide_encoder_lib.create_model(
        local_slide_encoder_path or "hf_hub:prov-gigapath/prov-gigapath",
        slide_arch,
        tile_model.embed_dim,
        global_pool=global_pool,
        dtype=jnp.bfloat16,
    )
    n_slide = sum(int(np.prod(p.shape)) for p in jax.tree.leaves(slide_params))
    console(f"Slide encoder param # {n_slide}")
    return (tile_model, tile_params), (slide_model, slide_params)


@functools.lru_cache(maxsize=8)
def tile_encode_fn(tile_encoder):
    """The jitted tile-batch forward ``encode(params, imgs [B, H, W, 3]) ->
    [B, 1536]`` that :func:`run_inference_with_tile_encoder` runs (params
    ride as an argument, never as 4 GB of inline constants)."""
    @jax.jit
    def tile_encode(params, imgs):
        return tile_encoder.apply({"params": params}, imgs)

    return tile_encode


@functools.lru_cache(maxsize=8)
def slide_forward_fn(slide_encoder_model):
    """The jitted all-layer slide forward ``(params, tile_embeds [B, N, D] bf16, coords [B, N, 2])
    -> per-layer embeddings`` that :func:`run_inference_with_slide_encoder` runs."""
    @jax.jit
    def slide_forward(params, tile_embeds, coords):
        return slide_encoder_model.apply(
            {"params": params}, tile_embeds, coords, all_layer_embed=True
        )

    return slide_forward


@functools.lru_cache(maxsize=8)
def lm_forward_fn(lm):
    """The jitted scoring forward ``(params, ids [B, L] int32, positions [B,
    P] int32) -> (logits [B, P, vocab] float32, tokens each held expert
    received [expert layers, experts_held] int32; ``()``, no counts, where the
    model has no expert layer)`` that :func:`run_inference_with_lm` runs, for
    any LM of the registry. The head runs on the rows ``positions`` names and on
    no other: all 16,384 rows of a document would be 3.3 GB of logits a request.
    A model that counts or predicts more returns a dict of named arrays as a
    third output, handed on as it is. One function a model (flax modules hash
    by their fields), so a second call traces nothing, as for the two above."""

    @jax.jit
    def lm_forward(params, ids, positions):
        return lm.apply({"params": params}, ids, positions)

    return lm_forward


# A request through any of the three ``run_inference_with_*`` entries is three
# halves: ``<entry>_to_device(host batch) -> device arguments``, the jitted
# function above, ``<entry>_to_host(outputs)``, called in that order by
# ``<entry>_request`` under the same six spans (``obs/spans.py``; no-ops unless
# a recorder or a runlog listens): ``request`` > ``prepare`` (host-side
# shaping), ``h2d`` (ends when the bytes are on the device), ``dispatch`` (the
# jitted call; JAX's ``trace`` / ``lower`` / ``compile`` beneath it on a first
# call), ``device_wait`` (ends when the outputs are ready), ``d2h`` (the
# conversions to numpy).


def tile_encoder_to_device(imgs: np.ndarray, batch_size: int = 128) -> tuple:
    """Host tiles ``[n <= batch_size, H, W, 3]`` -> the device arguments of
    :func:`tile_encode_fn`: padded to the compiled batch shape, bfloat16."""
    with span("prepare"):
        n = imgs.shape[0]
        if n < batch_size:  # pad to the compiled batch shape, slice after
            imgs = np.concatenate(
                [imgs, np.zeros((batch_size - n, *imgs.shape[1:]), imgs.dtype)]
            )
    with span("h2d", fence=True) as sp:
        return (sp.fence(jnp.asarray(imgs, jnp.bfloat16)),)


def tile_encoder_to_host(out, n: int) -> np.ndarray:
    """The first ``n`` embeddings of a batch, float32 on the host."""
    with span("device_wait", fence=out):
        pass
    with span("d2h"):
        return np.asarray(out if n == out.shape[0] else out[:n], np.float32)


def tile_encoder_request(encode, tile_params, imgs: np.ndarray,
                         batch_size: int = 128) -> np.ndarray:
    """One batch of host tiles -> ``[n, 1536]`` float32 on the host."""
    with span("request"):
        args = tile_encoder_to_device(imgs, batch_size)
        with span("dispatch"):
            out = encode(tile_params, *args)
        return tile_encoder_to_host(out, imgs.shape[0])


def run_inference_with_tile_encoder(
    image_paths: List[str],
    tile_encoder,
    tile_params=None,
    batch_size: int = 128,
) -> dict:
    """Encode tiles in fixed-size batches -> {'tile_embeds' [N, 1536],
    'coords' [N, 2]} (reference ``pipeline.py:140-162``).

    ``tile_encoder`` may be the ``(model, params)`` tuple from
    :func:`load_tile_slide_encoder` or a module with params passed
    separately."""
    if tile_params is None:
        tile_encoder, tile_params = tile_encoder
    dataset = TileEncodingDataset(
        image_paths,
        transform=load_tile_encoder_transforms(crop_size=tile_encoder.img_size),
    )

    encode = tile_encode_fn(tile_encoder)
    embeds, coords = [], []
    for start in range(0, len(dataset), batch_size):
        samples = [dataset[i] for i in range(start, min(start + batch_size, len(dataset)))]
        imgs = np.stack([s["img"] for s in samples])
        embeds.append(tile_encoder_request(encode, tile_params, imgs, batch_size))
        coords.append(np.stack([s["coords"] for s in samples]))
    return {
        "tile_embeds": np.concatenate(embeds),
        "coords": np.concatenate(coords).astype(np.float32),
    }


def run_inference_with_slide_encoder_streaming(
    chunks,
    n_tiles: int,
    slide_encoder_model=None,
    slide_params=None,
    *,
    chunk_tiles: Optional[int] = None,
) -> dict:
    """Streaming twin of :func:`run_inference_with_slide_encoder`: the
    chunk-granular ``LongNetViT`` entry. ``chunks`` is any iterable of
    ``(chunk_idx, tile_embeds [c, D], coords [c, 2])`` triples or
    :class:`~gigapath_tpu.dist.boundary.EmbeddingChunk` objects (arrival
    order free — the session frontier-buffers), cut by the deterministic
    ``chunk_bounds(n_tiles, chunk_tiles)`` plan. Each chunk folds into
    the encoder as it arrives (overlapping the producer with stage-2
    folding); the dense tile-embedding sequence is never materialized.
    Returns the same ``layer_{i}_embed`` / ``last_layer_embed`` dict as
    the dense entry, which stays the fallback and parity oracle."""
    from gigapath_tpu.models.streaming_encoder import (
        StreamingEncoderSession,
        embeds_to_outputs,
    )

    if slide_params is None:
        slide_encoder_model, slide_params = slide_encoder_model
    session = StreamingEncoderSession(
        slide_encoder_model, slide_params, int(n_tiles),
        chunk_tiles=chunk_tiles, all_layer_embed=True,
    )

    # the dense entry casts activations to bf16 before apply (the TPU
    # shape); the ONE shared helper (quant/qtensor.py) mirrors that
    # quantization per chunk so every entry — dense, streaming, and the
    # dist tile worker's real encoder — feeds the slide encoder
    # bit-identical inputs (parity-pinned in tests/test_quant.py)
    from gigapath_tpu.quant.qtensor import bf16_round_trip

    for item in chunks:
        if hasattr(item, "chunk_id"):  # EmbeddingChunk duck type
            session.feed(item.chunk_id, bf16_round_trip(item.payload),
                         item.coords)
        else:
            idx, embeds, coords = item
            session.feed(idx, bf16_round_trip(embeds), coords)
    return embeds_to_outputs(session.finalize())


def slide_encoder_to_device(tile_embeds: np.ndarray, coords: np.ndarray) -> tuple:
    """Host tile embeddings ``[N, D]`` or ``[B, N, D]`` and coordinates ->
    the device arguments of :func:`slide_forward_fn`: float32 to the device,
    cast to bfloat16 there."""
    with span("prepare"):
        tile_embeds, coords = (
            x if hasattr(x, "ndim") else np.asarray(x) for x in (tile_embeds, coords))
        if tile_embeds.ndim == 2:
            tile_embeds, coords = tile_embeds[None], coords[None]
    with span("h2d", fence=True) as sp:
        return sp.fence((
            jnp.asarray(tile_embeds).astype(jnp.bfloat16),
            jnp.asarray(coords, jnp.float32),
        ))


def slide_encoder_to_host(slide_embeds) -> dict:
    """Every layer's embedding, float32 on the host, keyed as the reference
    keys them."""
    with span("device_wait", fence=slide_embeds):
        pass
    with span("d2h"):
        outputs = {
            f"layer_{i}_embed": np.asarray(e, np.float32)
            for i, e in enumerate(slide_embeds)
        }
        outputs["last_layer_embed"] = np.asarray(slide_embeds[-1], np.float32)
        return outputs


def slide_encoder_request(forward, slide_params, tile_embeds: np.ndarray,
                          coords: np.ndarray) -> dict:
    """One batch of slides' tile embeddings -> all-layer slide embeddings."""
    with span("request"):
        args = slide_encoder_to_device(tile_embeds, coords)
        with span("dispatch"):
            slide_embeds = forward(slide_params, *args)
        return slide_encoder_to_host(slide_embeds)


def run_inference_with_slide_encoder(
    tile_embeds: np.ndarray,
    coords: np.ndarray,
    slide_encoder_model=None,
    slide_params=None,
) -> dict:
    """All-layer slide embedding from tile embeddings
    (reference ``pipeline.py:165-190``)."""
    if slide_params is None:
        slide_encoder_model, slide_params = slide_encoder_model
    return slide_encoder_request(
        slide_forward_fn(slide_encoder_model), slide_params, tile_embeds, coords)


def lm_to_device(token_ids: np.ndarray, positions: Optional[np.ndarray] = None) -> tuple:
    """``token_ids [L]`` or ``[B, L]`` and the rows wanted (the last where
    none is given) -> the device arguments of :func:`lm_forward_fn`, int32
    ``ids [B, L]`` and ``positions [B, P]``, and beside them the host's
    ``positions [B, P]``, which the answer hands back."""
    with span("prepare"):
        ids = np.atleast_2d(np.asarray(token_ids)).astype(np.int32)
        if positions is None:
            positions = np.full((ids.shape[0], 1), ids.shape[1] - 1)
        positions = np.broadcast_to(
            np.atleast_2d(np.asarray(positions)), (ids.shape[0], np.shape(positions)[-1])
        ).astype(np.int32)
    with span("h2d", fence=True) as sp:
        return sp.fence((jnp.asarray(ids), jnp.asarray(positions))), positions


def lm_to_host(outputs, positions) -> dict:
    """The entry's answer on the host; ``positions`` are the rows the logits
    are for, the host array :func:`lm_to_device` gave."""
    logits, received, *more = outputs
    with span("device_wait", fence=outputs):
        pass
    with span("d2h"):
        return {
            **{name: np.asarray(value) for extras in more for name, value in extras.items()},
            "logits": np.asarray(logits, np.float32),
            "positions": positions,
            "expert_tokens": np.asarray(received, np.int32),
        }


def lm_request(forward, lm_params, token_ids: np.ndarray,
               positions: Optional[np.ndarray] = None) -> dict:
    """One batch of token ids -> the logits at the rows asked for."""
    with span("request"):
        args, positions = lm_to_device(token_ids, positions)
        with span("dispatch"):
            outputs = forward(lm_params, *args)
        return lm_to_host(outputs, positions)


def run_inference_with_lm(
    token_ids: np.ndarray,
    positions: Optional[np.ndarray] = None,
    lm=None,
    lm_params=None,
) -> dict:
    """Score token ids with a causal LM of the registry (``granite_4_0_h_small``
    of ``models/granite_hybrid.py``, ``axk1`` of ``models/axk1.py``,
    ``deepseek_v32`` of ``models/deepseek_v32.py``, ``brumby`` of
    ``models/brumby.py``; any module with their contract, nothing here asks
    which): ``token_ids [L]`` or ``[B, L]`` int, ``positions [P]`` or ``[B, P]``
    the rows whose next-token logits are wanted (the last row where none is
    given). ``lm`` may be the ``(model, params)`` pair that
    ``models.granite_hybrid.create_lm`` returns. Returns ``{'logits' [B, P,
    vocab] float32, 'positions' [B, P], 'expert_tokens' [expert layers,
    experts_held] int32 (shape ``(0,)`` where the model has no expert layer)}``
    and whatever the third output names (``selected_pairs``, ``mtp_logits``;
    ``carried_share``)."""
    if lm_params is None:
        lm, lm_params = lm
    return lm_request(lm_forward_fn(lm), lm_params, token_ids, positions)
