"""LongNetViT slide encoder + factory.

Parity with reference ``gigapath/slide_encoder.py``: a MAE-style ViT over
tile *embeddings* — linear patch embed (1536 -> D), 2-D sincos positional
embedding looked up by tile coordinates, a cls token, a LongNet encoder, and
cls/global-pool readout per selected layer.

TPU-first deltas:

- the `(slide_ngrids^2+1, D)` positional table (~3 GB at defaults,
  ``slide_encoder.py:104``) is never materialized — embeddings are computed
  from coords on the fly with exact gather parity
  (:mod:`gigapath_tpu.ops.pos_embed`);
- ``get_optimal_segment_length`` (``slide_encoder.py:137-154``) returns the
  same log2-spaced schedule but as ints, and the model is built for a padded
  power-of-two bucket of sequence lengths so jit recompilation is bounded;
- bf16 activations via ``dtype=jnp.bfloat16`` replace fp16 GradScaler
  autocast;
- a chunk-granular entry (:func:`create_streaming_session`, streaming
  chunked prefill): tile chunks fold into the encoder as they arrive
  instead of assembling the dense ``[B, L, D]`` sequence first — the
  ``__call__`` path below stays the fallback and parity oracle
  (:mod:`gigapath_tpu.models.streaming_encoder`).
"""

from __future__ import annotations

import os
from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from gigapath_tpu.models.longnet import make_longnet_from_name
from gigapath_tpu.obs import console
from gigapath_tpu.ops import pos_embed as pe
from gigapath_tpu.utils.registry import create_model_from_registry, register_model
from gigapath_tpu.utils.torch_convert import (
    convert_state_dict,
    load_torch_state_dict,
    merge_into_params,
)


class PatchEmbed(nn.Module):
    """Linear projection of tile embeddings (reference ``PatchEmbed:32-51``)."""

    in_chans: int = 1536
    embed_dim: int = 768
    dtype: Any = None

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        return nn.Dense(
            self.embed_dim,
            dtype=self.dtype,
            kernel_init=nn.initializers.xavier_uniform(),
            name="proj",
        )(x)


def get_optimal_segment_length(max_wsi_size: int = 262144, tile_size: int = 256) -> List[int]:
    """Log2-spaced 5-segment schedule from the max WSI size
    (parity with reference ``slide_encoder.py:137-154``)."""
    max_seq_len = (max_wsi_size // tile_size) ** 2
    exponents = np.linspace(np.log2(1024), int(np.log2(max_seq_len)), 5)
    return [int(x) for x in np.power(2, exponents).astype(int)]


class LongNetViT(nn.Module):
    """Slide encoder over ``(tile_embeddings [B,L,in_chans], coords [B,L,2])``.

    Returns a list of slide-level embeddings (one per selected layer when
    ``all_layer_embed``, else just the final), each ``[B, embed_dim]``.
    """

    in_chans: int = 1536
    embed_dim: int = 768
    depth: int = 12
    slide_ngrids: int = 1000
    tile_size: int = 256
    max_wsi_size: int = 262144
    global_pool: bool = False
    dropout: float = 0.25
    drop_path_rate: float = 0.1
    norm_eps: float = 1e-6
    mlp_ratio: float = 4.0
    segment_length: Optional[Sequence[int]] = None
    dilated_ratio: str = "[1, 2, 4, 8, 16]"
    dtype: Any = None
    checkpoint_activations: bool = False
    seq_parallel: bool = False
    seq_axis_name: Optional[str] = None
    seq_axis_size: int = 1

    @property
    def encoder_name(self) -> str:
        name = f"LongNet_{self.depth}_layers_{self.embed_dim}_dim"
        if self.mlp_ratio != 4.0:
            name += f"_mlp{self.mlp_ratio:g}"
        return name

    def coords_to_pos(self, coords: jnp.ndarray) -> jnp.ndarray:
        return pe.coords_to_pos(coords, self.tile_size, self.slide_ngrids)

    @nn.compact
    def __call__(
        self,
        x: jnp.ndarray,
        coords: jnp.ndarray,
        all_layer_embed: bool = False,
        pad_mask: Optional[jnp.ndarray] = None,
        deterministic: bool = True,
    ) -> List[jnp.ndarray]:
        """``pad_mask``: optional [B, L] bool, True = VALID tile (the
        collate convention, data/collate.py). Padded suffix tokens are
        zeroed, excluded from every attention branch's keys, and excluded
        from the global-pool mean."""
        B, L, _ = x.shape
        x = PatchEmbed(self.in_chans, self.embed_dim, dtype=self.dtype, name="patch_embed")(x)

        # positional embedding computed from coords (no 3 GB table)
        pos = pe.pos_embed_for_coords(self.embed_dim, coords, self.tile_size, self.slide_ngrids)
        x = x + pos.astype(x.dtype)

        cls_token = self.param(
            "cls_token", nn.initializers.normal(0.02), (1, 1, self.embed_dim)
        )
        # cls positional embedding is table row 0 == zeros, so cls = cls_token
        cls = jnp.broadcast_to(cls_token.astype(x.dtype), (B, 1, self.embed_dim))
        x = jnp.concatenate([cls, x], axis=1)

        segment_length = self.segment_length or get_optimal_segment_length(
            self.max_wsi_size, self.tile_size
        )
        encoder, _ = make_longnet_from_name(
            self.encoder_name,
            dilated_ratio=self.dilated_ratio,
            segment_length=list(segment_length),
            drop_path_rate=self.drop_path_rate,
            dropout=self.dropout,
            dtype=self.dtype,
            seq_parallel=self.seq_parallel,
            seq_axis_name=self.seq_axis_name,
            seq_axis_size=self.seq_axis_size,
            checkpoint_activations=self.checkpoint_activations,
        )
        encoder = type(encoder)(args=encoder.args, dtype=self.dtype, name="encoder")

        encoder_padding_mask = None
        if pad_mask is not None:
            # cls (position 0) is always valid; model convention is True=pad
            encoder_padding_mask = jnp.concatenate(
                [jnp.zeros((B, 1), bool), ~pad_mask.astype(bool)], axis=1
            )

        # TPU alignment: L+1 (the cls token) is odd, which costs ~20% in the
        # attention kernels (odd segment reshapes defeat Mosaic tiling). Pad
        # the internal sequence to a 128 multiple with a *concrete* suffix
        # mask — a static valid length downstream, so the Pallas path and
        # trace-time tail masks absorb it for free. Skipped under sequence
        # parallelism (gather_kv branches don't take a valid length yet;
        # shard lengths are the caller's alignment concern there).
        L1 = x.shape[1]
        pad_to = L1 if self.seq_parallel else -(-L1 // 128) * 128
        if pad_to != L1:
            x = jnp.pad(x, ((0, 0), (0, pad_to - L1), (0, 0)))
            tail = np.zeros((B, pad_to), bool)
            tail[:, L1:] = True
            if encoder_padding_mask is None:
                encoder_padding_mask = tail
            else:
                encoder_padding_mask = jnp.concatenate(
                    [encoder_padding_mask, jnp.ones((B, pad_to - L1), bool)],
                    axis=1,
                )

        out = encoder(
            token_embeddings=x,
            encoder_padding_mask=encoder_padding_mask,
            return_all_hiddens=all_layer_embed,
            deterministic=deterministic,
        )
        x_list = out["encoder_states"] if all_layer_embed else [out["encoder_out"]]
        if pad_to != L1:
            x_list = [h[:, :L1] for h in x_list]

        norm = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype, name="norm")
        outcomes = []
        for h in x_list:
            if self.global_pool:
                if pad_mask is not None:
                    valid = pad_mask.astype(h.dtype)[..., None]
                    pooled = (h[:, 1:, :] * valid).sum(axis=1) / jnp.clip(
                        valid.sum(axis=1), 1.0
                    )
                else:
                    pooled = h[:, 1:, :].mean(axis=1)
                outcomes.append(norm(pooled))
            else:
                outcomes.append(norm(h)[:, 0])
        return outcomes

    def __post_init__(self):
        # a tuple, whatever sequence the caller gave: the module hashes by its
        # fields, and ``pipeline.slide_forward_fn`` keeps one function a model
        if self.segment_length is not None:
            object.__setattr__(self, "segment_length", tuple(self.segment_length))
        super().__post_init__()


def _arch(defaults: dict, kwargs: dict) -> LongNetViT:
    return LongNetViT(**{**defaults, **kwargs})


@register_model
def gigapath_slide_enc12l768d(**kwargs):
    return _arch(dict(embed_dim=768, depth=12, mlp_ratio=4.0, norm_eps=1e-6), kwargs)


@register_model
def gigapath_slide_enc24l1024d(**kwargs):
    return _arch(dict(embed_dim=1024, depth=24, mlp_ratio=4.0, norm_eps=1e-6), kwargs)


@register_model
def gigapath_slide_enc12l1536d(**kwargs):
    return _arch(dict(embed_dim=1536, depth=12, mlp_ratio=4.0, norm_eps=1e-6), kwargs)


@register_model
def gigapath_slide_enc_tiny(**kwargs):
    """2-layer/32-dim smoke-test arch (parallel of ``LongNet_test``,
    reference LongNetConfig.py:321-334)."""
    return _arch(
        dict(
            embed_dim=32,
            depth=2,
            mlp_ratio=2.0,
            norm_eps=1e-6,
            segment_length=[16, 32],
            dilated_ratio="[1, 2]",
        ),
        kwargs,
    )


def create_streaming_session(
    model: LongNetViT,
    params,
    n_tiles: int,
    *,
    chunk_tiles: Optional[int] = None,
    all_layer_embed: bool = False,
):
    """The chunk-granular ``LongNetViT`` entry (streaming chunked
    prefill): returns a
    :class:`~gigapath_tpu.models.streaming_encoder.StreamingEncoderSession`
    whose ``feed(idx, tile_embeds, coords)`` consumes the deterministic
    chunk plan in any arrival order and whose ``finalize()`` returns the
    same output list as ``model.apply`` — which remains the dense
    fallback and parity oracle. ``chunk_tiles`` defaults to the
    ``GIGAPATH_PREFILL_CHUNK`` host flag."""
    from gigapath_tpu.models.streaming_encoder import StreamingEncoderSession

    return StreamingEncoderSession(
        model, params, n_tiles, chunk_tiles=chunk_tiles,
        all_layer_embed=all_layer_embed,
    )


def init_params(model: LongNetViT, rng: Optional[jax.Array] = None, seq_len: int = 4):
    """Initialize a param tree (tiny dummy inputs; shapes are L-independent).

    Init runs under ``jit``: eager flax init dispatches each initializer as
    its own device op (and its own small compile); jitted it is one
    program."""
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    x = jnp.zeros((1, seq_len, model.in_chans), jnp.float32)
    coords = jnp.zeros((1, seq_len, 2), jnp.float32)
    variables = jax.jit(model.init)(rng, x, coords)
    # No sub-LN init rescale here: the reference's initialize_vit_weights
    # re-inits every nn.Linear with xavier_uniform AFTER the encoder applied
    # its sub-LN scaling (slide_encoder.py:134-135 overwrites
    # encoder.py:254-270), so the effective reference init is plain xavier —
    # which is exactly what the flax modules use. apply_init_scaling remains
    # available for standalone make_longnet() users (parity with that path).
    return variables["params"]


def create_model(
    pretrained: str = "",
    model_arch: str = "gigapath_slide_enc12l768d",
    in_chans: int = 1536,
    *,
    rng: Optional[jax.Array] = None,
    **kwargs,
):
    """Build a slide encoder and optionally load a (torch) checkpoint.

    Returns ``(module, params)``. Parity with reference ``create_model:226``:
    local ``slide_encoder.pth`` paths load non-strictly with missing /
    unexpected key reporting; absent checkpoints leave random init with a
    warning. (HF-hub download is out of scope in the zero-egress build; pass
    a local path.)
    """
    model = create_model_from_registry(model_arch, in_chans=in_chans, **kwargs)
    params = init_params(model, rng=rng)

    local_path = pretrained
    if pretrained.startswith("hf_hub:"):
        cached = os.path.join(os.path.expanduser("~"), ".cache", "slide_encoder.pth")
        local_path = cached

    if local_path and os.path.exists(local_path):
        state = load_torch_state_dict(local_path)
        converted = convert_state_dict(state)
        params, missing, unexpected = merge_into_params(params, converted)
        console(
            f"\033[92m Successfully loaded pretrained GigaPath slide encoder "
            f"from {local_path} ({len(missing)} missing, {len(unexpected)} unexpected) \033[00m"
        )
    elif pretrained:
        console(
            f"\033[93m Pretrained weights not found at {local_path}. "
            f"Randomly initialized the model! \033[00m"
        )
    return model, params
