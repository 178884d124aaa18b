"""Flax ViT-G/14 tile encoder (DINOv2-style) + timm checkpoint conversion.

The reference consumes the tile encoder entirely through timm
(``timm.create_model("hf_hub:prov-gigapath/prov-gigapath")``,
``gigapath/pipeline.py:126-128``); the architecture itself lives outside the
reference repo. The facts the reference pins: "ViT-G/14" with 1536-d output
(``README.md:83``), ~1.13 B params printed at load (``gigapath/pipeline.py:129``),
224 px input after resize-256/center-crop-224 (``gigapath/pipeline.py:106-115``).
The timm architecture matching those facts is ``vit_giant_patch14_dinov2``
overridden to patch 16 / embed 1536 / depth 40 / 24 heads / SwiGLU
(mlp_ratio 5.33334) / LayerScale: per-block params
qkv 7,082,496 + proj 2,360,832 + norms 6,144 + layerscales 3,072 +
swiglu-fc1 12,591,104 + swiglu-fc2 6,292,992 = 28,336,640; x40 plus patch
embed (1,181,184), cls (1,536), pos (302,592), final norm (3,072) =
**1,134,953,984** — the unique configuration reproducing the printed count
(a standard GELU MLP would give 1.39 B). Verified in
``tests/test_tile_encoder.py``.

TPU-first notes: on a TPU, where the heads tile the 128-lane groups and the
whole sequence fits VMEM (ViT-G/14: 24 heads of 64 over 197 tokens), the
attention core is one Pallas kernel over the packed qkv output
(``ops/pallas_vit_attention.py``); everywhere else (the CPU, heads of 8, a
sequence too long) it rides the shared ``attention_with_lse`` — fp32 softmax
statistics, bf16-safe, in both; there is no interpolate-at-forward —
positional embeddings are resized once at conversion time so every shape
under ``jit`` is static; ``param_dtype`` lets the 1.13 B params live in bf16
end-to-end (no fp16 GradScaler needed on TPU).
"""

from __future__ import annotations

import math
import os
import re
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn

from gigapath_tpu.obs import console
from gigapath_tpu.ops import flash_attention, pallas_vit_attention
from gigapath_tpu.ops.droppath import DropPath
from gigapath_tpu.utils.registry import create_model_from_registry, register_model
from gigapath_tpu.utils.torch_convert import (
    convert_torch_entry,
    load_torch_state_dict,
    merge_into_params,
)

# ImageNet normalization used by the reference's tile transforms
# (gigapath/pipeline.py:113-114).
IMAGENET_MEAN = (0.485, 0.456, 0.406)
IMAGENET_STD = (0.229, 0.224, 0.225)


class PatchEmbedConv(nn.Module):
    """Conv patch embedding: [B, H, W, 3] -> [B, N, D] (timm ``patch_embed``)."""

    patch_size: int = 16
    embed_dim: int = 1536
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = nn.Conv(
            self.embed_dim,
            kernel_size=(self.patch_size, self.patch_size),
            strides=(self.patch_size, self.patch_size),
            padding="VALID",
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="proj",
        )(x)
        B, h, w, D = x.shape
        return x.reshape(B, h * w, D)


class LayerScale(nn.Module):
    """Per-channel learned residual scale (DINOv2 ``ls1``/``ls2``)."""

    dim: int
    init_values: float = 1e-5
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        gamma = self.param(
            "gamma",
            nn.initializers.constant(self.init_values),
            (self.dim,),
            self.param_dtype,
        )
        return x * gamma.astype(x.dtype)


def _dense(features: int, *, quant: str, quant_pallas: bool, dtype,
           param_dtype, name: str):
    """The quantized-tier seam: ``nn.Dense`` when ``quant`` is empty
    (the f32/bf16 fallback and parity oracle — byte-identical trace to
    the pre-quant program), else the ``QuantDense`` twin (same param
    names/shapes, so checkpoints and the sharding-rule name lists are
    oblivious). ``quant``/``quant_pallas`` come from the module's
    fields — never from the environment here."""
    if not quant:
        return nn.Dense(
            features, dtype=dtype, param_dtype=param_dtype, name=name
        )
    from gigapath_tpu.quant.qmatmul import QuantDense

    return QuantDense(
        features, mode=quant, use_pallas=quant_pallas, dtype=dtype,
        param_dtype=param_dtype, name=name,
    )


class ViTAttention(nn.Module):
    """Packed-qkv multi-head self-attention (timm ``Attention``).

    Between ``qkv`` and ``proj`` (the ``attn_core`` scope) one of two forms
    runs, chosen from what the code can observe and by no flag: on a TPU
    (``ops.flash_attention._on_tpu``) with shapes the kernel takes
    (``pallas_vit_attention.fits``), ``packed_qkv_attention`` reads the
    ``[B, N, 3*D]`` array as the GEMM wrote it and writes ``[B, N, D]``;
    otherwise its jnp form, the split into q, k, v and ``attention_with_lse``.

    ``quant`` routes the qkv/proj matmuls through the quantized tier
    (gigapath_tpu/quant/); the ``+attn`` rider additionally computes
    the attention logits from dynamically-quantized int8 Q/K
    (quant/qflash.py) — f32 softmax statistics either way."""

    dim: int
    num_heads: int
    quant: str = ""
    quant_pallas: bool = False
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        B, N, D = x.shape
        H = self.num_heads
        hd = D // H
        qkv = _dense(
            3 * D, quant=self.quant, quant_pallas=self.quant_pallas,
            dtype=self.dtype, param_dtype=self.param_dtype, name="qkv"
        )(x)
        with jax.named_scope("attn_core"):
            if self.quant and self.quant.endswith("+attn"):
                from gigapath_tpu.quant.qflash import q_flash_attention

                qkv = qkv.reshape(B, N, 3, H, hd)
                out, _ = q_flash_attention(
                    qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2],
                    use_pallas=self.quant_pallas,
                )
                out = out.reshape(B, N, D)
            elif flash_attention._on_tpu() and pallas_vit_attention.fits(
                qkv.shape, H, qkv.dtype
            ):
                out = pallas_vit_attention.packed_qkv_attention(qkv, H)
            else:
                out = pallas_vit_attention.packed_qkv_attention_jnp(qkv, H)
        return _dense(
            D, quant=self.quant, quant_pallas=self.quant_pallas,
            dtype=self.dtype, param_dtype=self.param_dtype, name="proj"
        )(out)


class SwiGLUPacked(nn.Module):
    """Packed SwiGLU MLP: fc1 -> chunk2 -> silu(x1) * x2 -> fc2 (timm
    ``SwiGLUPacked``/``GluMlp(gate_last=False)``)."""

    hidden_dim: int
    out_dim: int
    quant: str = ""
    quant_pallas: bool = False
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = _dense(
            self.hidden_dim, quant=self.quant,
            quant_pallas=self.quant_pallas, dtype=self.dtype,
            param_dtype=self.param_dtype, name="fc1"
        )(x)
        x1, x2 = jnp.split(x, 2, axis=-1)
        x = nn.silu(x1) * x2
        return _dense(
            self.out_dim, quant=self.quant,
            quant_pallas=self.quant_pallas, dtype=self.dtype,
            param_dtype=self.param_dtype, name="fc2"
        )(x)


class Mlp(nn.Module):
    """Standard ViT MLP: fc1 -> gelu -> fc2 (timm ``Mlp``)."""

    hidden_dim: int
    out_dim: int
    quant: str = ""
    quant_pallas: bool = False
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        x = _dense(
            self.hidden_dim, quant=self.quant,
            quant_pallas=self.quant_pallas, dtype=self.dtype,
            param_dtype=self.param_dtype, name="fc1"
        )(x)
        x = nn.gelu(x, approximate=False)
        return _dense(
            self.out_dim, quant=self.quant,
            quant_pallas=self.quant_pallas, dtype=self.dtype,
            param_dtype=self.param_dtype, name="fc2"
        )(x)


class ViTBlock(nn.Module):
    """Pre-norm transformer block with LayerScale + DropPath (timm/DINOv2)."""

    dim: int
    num_heads: int
    mlp_hidden_dim: int
    swiglu: bool = True
    init_values: Optional[float] = 1e-5
    drop_path: float = 0.0
    norm_eps: float = 1e-6
    quant: str = ""
    quant_pallas: bool = False
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        ln = lambda name: nn.LayerNorm(  # noqa: E731
            epsilon=self.norm_eps,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name=name,
        )
        dp = DropPath(drop_prob=self.drop_path)
        h = ViTAttention(
            self.dim,
            self.num_heads,
            quant=self.quant,
            quant_pallas=self.quant_pallas,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="attn",
        )(ln("norm1")(x))
        if self.init_values is not None:
            h = LayerScale(
                self.dim, self.init_values, param_dtype=self.param_dtype, name="ls1"
            )(h)
        x = x + dp(h, deterministic=deterministic)

        mlp_cls = SwiGLUPacked if self.swiglu else Mlp
        h = mlp_cls(
            self.mlp_hidden_dim,
            self.dim,
            quant=self.quant,
            quant_pallas=self.quant_pallas,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="mlp",
        )(ln("norm2")(x))
        if self.init_values is not None:
            h = LayerScale(
                self.dim, self.init_values, param_dtype=self.param_dtype, name="ls2"
            )(h)
        return x + dp(h, deterministic=deterministic)


class VisionTransformer(nn.Module):
    """DINOv2-style ViT: conv patch embed + cls token + learned pos embed +
    pre-norm blocks + final LN; ``token`` pooling returns the normed cls.

    ``__call__(images [B, H, W, 3]) -> [B, embed_dim]`` (num_classes=0 /
    feature mode, which is how the reference uses the tile encoder).
    ``forward_features`` returns all tokens ``[B, 1+N, D]`` for PCA-style
    visualization (reference ``demo/gigapath_pca_visualization*.py``).
    """

    img_size: int = 224
    patch_size: int = 16
    embed_dim: int = 1536
    depth: int = 40
    num_heads: int = 24
    mlp_ratio: float = 5.33334
    swiglu: bool = True
    init_values: Optional[float] = 1e-5
    drop_path_rate: float = 0.0
    norm_eps: float = 1e-6
    global_pool: str = "token"
    # quantized-weight tier ('' = off — the f32/bf16 fallback and parity
    # oracle; 'int8' / 'fp8_e4m3', optionally '+attn'): what
    # create_tile_encoder read from GIGAPATH_QUANT_TILE, passed at
    # construction so the traced program — and therefore the jit cache
    # key — is distinct per tier
    quant: str = ""
    quant_pallas: bool = False
    dtype: Any = None
    param_dtype: Any = jnp.float32

    @property
    def grid_size(self) -> int:
        return self.img_size // self.patch_size

    @property
    def num_patches(self) -> int:
        return self.grid_size**2

    @property
    def mlp_hidden_dim(self) -> int:
        return int(self.embed_dim * self.mlp_ratio)

    @nn.compact
    def forward_features(
        self, x: jnp.ndarray, deterministic: bool = True
    ) -> jnp.ndarray:
        B = x.shape[0]
        x = PatchEmbedConv(
            self.patch_size,
            self.embed_dim,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="patch_embed",
        )(x)
        cls_token = self.param(
            "cls_token",
            nn.initializers.normal(1e-6),
            (1, 1, self.embed_dim),
            self.param_dtype,
        )
        pos_embed = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (1, 1 + self.num_patches, self.embed_dim),
            self.param_dtype,
        )
        cls = jnp.broadcast_to(cls_token.astype(x.dtype), (B, 1, self.embed_dim))
        x = jnp.concatenate([cls, x], axis=1)
        x = x + pos_embed.astype(x.dtype)

        dpr = np.linspace(0.0, self.drop_path_rate, self.depth)
        for i in range(self.depth):
            x = ViTBlock(
                self.embed_dim,
                self.num_heads,
                self.mlp_hidden_dim,
                swiglu=self.swiglu,
                init_values=self.init_values,
                drop_path=float(dpr[i]),
                norm_eps=self.norm_eps,
                quant=self.quant,
                quant_pallas=self.quant_pallas,
                dtype=self.dtype,
                param_dtype=self.param_dtype,
                name=f"blocks_{i}",
            )(x, deterministic=deterministic)
        return nn.LayerNorm(
            epsilon=self.norm_eps,
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            name="norm",
        )(x)

    def __call__(self, x: jnp.ndarray, deterministic: bool = True) -> jnp.ndarray:
        x = self.forward_features(x, deterministic=deterministic)
        if self.global_pool == "avg":
            return x[:, 1:].mean(axis=1)
        return x[:, 0]


# --------------------------------------------------------------------------
# timm checkpoint conversion


def interpolate_pos_embed(
    pos_embed: np.ndarray, new_grid: int
) -> np.ndarray:
    """Bicubic-resize a [1, 1+g*g, D] pos table to [1, 1+new_grid^2, D].

    Counterpart of reference ``gigapath/pos_embed.py:85`` (torch
    ``F.interpolate(mode="bicubic")``), applied once at conversion time so
    forward shapes stay static under jit.
    """
    n_tok = pos_embed.shape[1] - 1
    g = int(math.isqrt(n_tok))
    assert g * g == n_tok, f"pos_embed patch count {n_tok} is not square"
    if g == new_grid:
        return pos_embed
    cls, patches = pos_embed[:, :1], pos_embed[:, 1:]
    D = patches.shape[-1]
    grid = patches.reshape(g, g, D)
    resized = jax.image.resize(
        jnp.asarray(grid, jnp.float32), (new_grid, new_grid, D), method="bicubic"
    )
    resized = np.asarray(resized).reshape(1, new_grid * new_grid, D)
    return np.concatenate([cls, resized], axis=1).astype(pos_embed.dtype)


def convert_timm_state_dict(
    state_dict: Dict[str, Any], target_grid: Optional[int] = None
) -> Dict[Tuple[str, ...], np.ndarray]:
    """timm ViT state dict -> ``{flax path: array}``.

    Handles the timm naming (``blocks.N.`` module lists, packed ``qkv``,
    ``ls1.gamma``); Linear kernels transpose and the patch-embed conv moves
    OIHW -> HWIO via :func:`convert_torch_entry`. ``target_grid`` resizes the
    positional table when checkpoint and model grids differ.
    """
    out: Dict[Tuple[str, ...], np.ndarray] = {}
    for key, value in state_dict.items():
        if key.startswith("head.") or key in ("mask_token",):
            continue  # feature mode: no classifier head
        key = re.sub(r"\bblocks\.(\d+)\b", r"blocks_\1", key)
        path, arr = convert_torch_entry(key, value)
        if path[0] == "pos_embed" and target_grid is not None:
            arr = interpolate_pos_embed(arr, target_grid)
        out[path] = arr
    return out


# --------------------------------------------------------------------------
# factories


@register_model
def gigapath_tile_enc(**kwargs) -> VisionTransformer:
    """The prov-gigapath ViT-G/14 tile encoder (1,134,953,984 params)."""
    defaults = dict(
        img_size=224,
        patch_size=16,
        embed_dim=1536,
        depth=40,
        num_heads=24,
        mlp_ratio=5.33334,
        swiglu=True,
        init_values=1e-5,
    )
    return VisionTransformer(**{**defaults, **kwargs})


@register_model
def vit_tile_enc_test(**kwargs) -> VisionTransformer:
    """Tiny smoke-test tile encoder (parallel of ``LongNet_test``)."""
    defaults = dict(
        img_size=32,
        patch_size=16,
        embed_dim=32,
        depth=2,
        num_heads=4,
        mlp_ratio=4.0,
        swiglu=True,
        init_values=1e-5,
    )
    return VisionTransformer(**{**defaults, **kwargs})


def init_params(
    model: VisionTransformer, rng: Optional[jax.Array] = None
) -> Dict[str, Any]:
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    x = jnp.zeros((1, model.img_size, model.img_size, 3), jnp.float32)
    # under jit: eager flax init dispatches every initializer (and the
    # batch-1 forward behind it) as its own device op and compile
    return jax.jit(model.init)(rng, x)["params"]


def create_tile_encoder(
    pretrained: str = "",
    model_arch: str = "gigapath_tile_enc",
    *,
    rng: Optional[jax.Array] = None,
    **kwargs,
):
    """Build the tile encoder and optionally load a timm torch checkpoint.

    Returns ``(module, params)``; non-strict load with missing/unexpected key
    reporting, matching the slide-encoder factory and the reference's timm
    ``checkpoint_path`` loading (``gigapath/pipeline.py:126``).

    Quant tier: when the caller passes neither a ``quant`` nor a
    ``quant_pallas`` kwarg, the factory reads ``GIGAPATH_QUANT_TILE`` /
    ``GIGAPATH_QUANT_PALLAS`` here, once, host side. An explicit kwarg
    pins the tier regardless; with no env and no kwarg the result is the
    f32/bf16 program (the parity oracle).
    """
    model = create_model_from_registry(model_arch, **kwargs)
    if "quant" not in kwargs and "quant_pallas" not in kwargs:
        from gigapath_tpu.ops.common import env_flag
        from gigapath_tpu.quant.qtensor import normalize_mode

        quant = normalize_mode(os.environ.get("GIGAPATH_QUANT_TILE", ""))
        if quant:
            # rebuild with the tier (module construction is a frozen
            # dataclass — params are untouched); the common no-tier
            # path keeps the one construction above
            model = create_model_from_registry(
                model_arch, quant=quant,
                quant_pallas=env_flag("GIGAPATH_QUANT_PALLAS"), **kwargs,
            )
    params = init_params(model, rng=rng)
    if pretrained and os.path.isdir(pretrained) and os.path.exists(
        os.path.join(pretrained, "manifest.json")
    ):
        # a quantized artifact (quant/convert.py): manifest-verified
        # load, then the f32 dequant contract back into the param tree
        # (QuantDense re-quantizes in-graph to the identical grid —
        # the round-trip is idempotent by construction)
        from gigapath_tpu.quant.convert import (
            _walk,
            dequantize_params,
            load_quantized,
        )

        qparams, qmeta = load_quantized(pretrained)
        converted = dict(_walk(dequantize_params(qparams)))
        params, missing, unexpected = merge_into_params(params, converted)
        console(
            f"\033[92m Loaded quantized tile-encoder artifact from "
            f"{pretrained} (mode={qmeta.get('mode')}, "
            f"{qmeta.get('n_quantized')} quantized kernels, "
            f"{len(missing)} missing, {len(unexpected)} unexpected) \033[00m"
        )
        return model, params
    if pretrained and os.path.exists(pretrained):
        state = load_torch_state_dict(pretrained)
        converted = convert_timm_state_dict(state, target_grid=model.grid_size)
        params, missing, unexpected = merge_into_params(params, converted)
        console(
            f"\033[92m Successfully loaded tile encoder from {pretrained} "
            f"({len(missing)} missing, {len(unexpected)} unexpected) \033[00m"
        )
    elif pretrained:
        console(
            f"\033[93m Tile-encoder weights not found at {pretrained}. "
            f"Randomly initialized the model! \033[00m"
        )
    return model, params


def count_params(model: VisionTransformer) -> int:
    """Analytic param count via abstract init (no 1.13 B-param allocation)."""
    x = jax.ShapeDtypeStruct((1, model.img_size, model.img_size, 3), jnp.float32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)
    return sum(int(np.prod(p.shape)) for p in jax.tree.leaves(shapes))
