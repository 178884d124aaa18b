"""Plain references for the benchmark's configurations.

Straightforward ``jax.numpy`` in float32 with every matrix product at
``highest`` precision: no kernels, no cache, no batching tricks, one slide or
one block of tiles at a time. Nothing here imports the program; the weights
are the benchmark's own (``lib/weights.py``), read by the names of the
program's parameter tree, which is the one interface the two share.

``mode`` selects the precision of every matrix product and is how the
controls are made (the reference put in the program's place, one precision
down):

- ``f32``  float32 operands, ``highest`` (the reference proper);
- ``bf16`` operands rounded to bfloat16, float32 accumulation (what the
  configurations state);
- ``int8`` operands rounded to a symmetric int8 grid, one scale per row of
  the left operand and per column of the right one;
- ``fp8``  the same with the operands rounded to float8 e4m3 (int8 and fp8
  are the two precisions next below bfloat16; a cell's file names the one its
  control uses).

Published descriptions followed: DINOv2-style ViT-G (timm
``vit_giant_patch14_dinov2`` at patch 16 with packed SwiGLU and LayerScale)
and LongNet (torchscale ``DilatedAttention``: per-branch segments, head-phased
dilation, branches fused by their softmax denominators) under the GigaPath
slide encoder (linear patch embedding, 2-D sin-cos positions looked up by
tile coordinate, a class token, sub-LayerNorm, erf GELU). Departures are
listed in ``PERF.md``.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

MODES = ("f32", "bf16", "int8", "fp8")
_HI = jax.lax.Precision.HIGHEST


def _fake_int8(x, axis):
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return jnp.round(x / scale) * scale


def _fake_fp8(x, axis):
    top = float(jnp.finfo(jnp.float8_e4m3fn).max)
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm(a, b, mode):
    """``a [..., M, K] @ b [..., K, N]`` in the precision ``mode`` names."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    if mode == "bf16":
        a = a.astype(jnp.bfloat16).astype(jnp.float32)
        b = b.astype(jnp.bfloat16).astype(jnp.float32)
    elif mode == "int8":
        a = _fake_int8(a, -1)
        b = _fake_int8(b, -2)
    elif mode == "fp8":
        a = _fake_fp8(a, -1)
        b = _fake_fp8(b, -2)
    elif mode != "f32":
        raise ValueError(f"unknown precision mode {mode!r}; known: {MODES}")
    return jnp.matmul(a, b, precision=_HI)


def dense(p, x, mode):
    return mm(x, p["kernel"], mode) + p["bias"].astype(jnp.float32)


def layer_norm(p, x, eps):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    y = (x - mu) / jnp.sqrt(var + eps)
    return y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)


def softmax_attention(q, k, v, mode, key_valid=None):
    """``q [..., Lq, D]``, ``k, v [..., Lk, D]`` -> (out, lse). ``key_valid``
    broadcasts to ``[..., 1, Lk]``; rows with no valid key give out 0 and
    lse -inf."""
    s = mm(q, jnp.swapaxes(k, -1, -2), mode) * (q.shape[-1] ** -0.5)
    if key_valid is not None:
        s = jnp.where(key_valid, s, -jnp.inf)
    top = jnp.max(s, axis=-1, keepdims=True)
    top = jnp.where(jnp.isfinite(top), top, 0.0)
    e = jnp.exp(s - top)
    den = e.sum(-1, keepdims=True)
    out = mm(e / jnp.where(den > 0, den, 1.0), v, mode)
    lse = jnp.where(den > 0, jnp.log(jnp.where(den > 0, den, 1.0)) + top, -jnp.inf)
    return out, lse[..., 0]


# --------------------------------------------------------------------------
# ViT-G/14 tile encoder


def _vit_embed(params, imgs, patch, mode):
    B, H, W, C = imgs.shape
    gh, gw = H // patch, W // patch
    x = imgs.astype(jnp.float32).reshape(B, gh, patch, gw, patch, C)
    x = x.transpose(0, 1, 3, 2, 4, 5).reshape(B, gh * gw, patch * patch * C)
    proj = params["patch_embed"]["proj"]
    kernel = proj["kernel"].reshape(patch * patch * C, -1)
    x = mm(x, kernel, mode) + proj["bias"].astype(jnp.float32)
    cls = jnp.broadcast_to(params["cls_token"].astype(jnp.float32), (B, 1, x.shape[-1]))
    return jnp.concatenate([cls, x], axis=1) + params["pos_embed"].astype(jnp.float32)


def _vit_block(bp, x, heads, eps, mode):
    B, N, D = x.shape
    qkv = dense(bp["attn"]["qkv"], layer_norm(bp["norm1"], x, eps), mode)
    qkv = qkv.reshape(B, N, 3, heads, D // heads).transpose(2, 0, 3, 1, 4)
    out, _ = softmax_attention(qkv[0], qkv[1], qkv[2], mode)  # [B, H, N, hd]
    out = out.transpose(0, 2, 1, 3).reshape(B, N, D)
    x = x + dense(bp["attn"]["proj"], out, mode) * bp["ls1"]["gamma"].astype(jnp.float32)
    h = dense(bp["mlp"]["fc1"], layer_norm(bp["norm2"], x, eps), mode)
    gate, val = jnp.split(h, 2, axis=-1)
    h = dense(bp["mlp"]["fc2"], jax.nn.silu(gate) * val, mode)
    return x + h * bp["ls2"]["gamma"].astype(jnp.float32)


_vit_embed_jit = jax.jit(_vit_embed, static_argnums=(2, 3))
_vit_block_jit = jax.jit(_vit_block, static_argnums=(2, 3, 4))
_layer_norm_jit = jax.jit(layer_norm, static_argnums=(2,))


def vit_forward(params, imgs, sizes, mode="f32", block_rows=32):
    """``imgs [B, H, W, 3]`` float32 -> ``[B, embed_dim]`` float32 on the
    host: the class token after the final norm. ``sizes`` is the
    configuration file's ``sizes`` object."""
    heads, eps = int(sizes["num_heads"]), float(sizes["norm_eps"])
    outs = []
    for start in range(0, imgs.shape[0], block_rows):
        x = _vit_embed_jit(params, jnp.asarray(imgs[start:start + block_rows]),
                           int(sizes["patch_size"]), mode)
        for i in range(int(sizes["depth"])):
            x = _vit_block_jit(params[f"blocks_{i}"], x, heads, eps, mode)
        outs.append(np.asarray(_layer_norm_jit(params["norm"], x[:, 0], eps)))
    return np.concatenate(outs)


# --------------------------------------------------------------------------
# LongNet slide encoder


def sincos_2d(embed_dim, coords, tile_size, ngrids):
    """The row of the published ``(ngrids^2, D)`` sin-cos table that tile
    coordinates ``[N, 2]`` select, in float64 on the host: the first half of
    the channels encodes the second coordinate's grid index."""
    grid = np.floor(np.asarray(coords, np.float64) / float(tile_size))
    flat = grid[:, 0] * ngrids + grid[:, 1]
    i, j = np.floor_divide(flat, ngrids), np.mod(flat, ngrids)

    def one(d, pos):
        omega = 1.0 / 10000 ** (np.arange(d // 2, dtype=np.float64) / (d / 2.0))
        arg = pos[:, None] * omega[None, :]
        return np.concatenate([np.sin(arg), np.cos(arg)], axis=1)

    half = embed_dim // 2
    return np.concatenate([one(half, j), one(half, i)], axis=1).astype(np.float32)


def dilated_attention(q, k, v, segment_lengths, ratios, mode):
    """LongNet dilated attention on one sequence, ``q, k, v [L, H, D]``.

    Branch ``(s, r)``: the sequence is cut into segments of ``min(s, L)``;
    in each, head ``h`` keeps the positions ``p + r*j`` with phase
    ``p = h // ceil(H / r)`` and attends among them; a position a head does
    not keep gets no output from that branch. Branches are fused per
    position and head by the softmax of their log-sum-exps."""
    L, H, D = q.shape
    outs, lses = [], []
    for s, r in zip(segment_lengths, ratios):
        g = min(int(s), L)
        n = -(-L // g)
        pad = n * g - L

        def seg(x):
            x = jnp.pad(x, ((0, pad), (0, 0), (0, 0)))
            return x.reshape(n, g, H, D)

        q4, k4, v4 = seg(q), seg(k), seg(v)
        pos = np.arange(n * g).reshape(n, g)
        out_b = jnp.zeros((n, g, H, D), jnp.float32)
        lse_b = jnp.full((n, g, H), -jnp.inf, jnp.float32)
        per_group = -(-H // r)
        for p in range(r):
            hs, he = p * per_group, min((p + 1) * per_group, H)
            if hs >= H:
                break
            take = lambda x: x[:, p::r, hs:he].transpose(0, 2, 1, 3)  # noqa: E731
            valid = jnp.asarray(pos[:, p::r] < L)[:, None, None, :]
            o, l = softmax_attention(take(q4), take(k4), take(v4), mode, valid)
            out_b = out_b.at[:, p::r, hs:he].set(o.transpose(0, 2, 1, 3))
            lse_b = lse_b.at[:, p::r, hs:he].set(l.transpose(0, 2, 1))
        outs.append(out_b.reshape(n * g, H, D)[:L])
        lses.append(lse_b.reshape(n * g, H)[:L])
    w = jax.nn.softmax(jnp.stack(lses), axis=0)  # [branches, L, H]
    return sum(o * wi[..., None] for o, wi in zip(outs, w))


def _gelu(x, kind):
    if kind == "erf":
        return jax.nn.gelu(x, approximate=False)
    if kind == "tanh":
        return jax.nn.gelu(x, approximate=True)
    raise ValueError(f"unknown gelu {kind!r}")


def _longnet_layer(lp, x, heads, eps, segs, ratios, gelu, mode):
    L, E = x.shape
    a = lp["self_attn"]
    h = layer_norm(lp["self_attn_layer_norm"], x, eps)
    q, k, v = (dense(a[n], h, mode).reshape(L, heads, E // heads)
               for n in ("q_proj", "k_proj", "v_proj"))
    h = dilated_attention(q, k, v, segs, ratios, mode).reshape(L, E)
    x = x + dense(a["out_proj"], layer_norm(a["inner_attn_ln"], h, eps), mode)
    f = lp["ffn"]
    h = _gelu(dense(f["fc1"], layer_norm(lp["final_layer_norm"], x, eps), mode), gelu)
    return x + dense(f["fc2"], layer_norm(f["ffn_layernorm"], h, eps), mode)


def _slide_embed(params, feats, pos, mode):
    x = dense(params["patch_embed"]["proj"], feats, mode) + pos
    return jnp.concatenate([params["cls_token"].astype(jnp.float32)[0], x], axis=0)


_longnet_layer_jit = jax.jit(_longnet_layer, static_argnums=(2, 3, 4, 5, 6, 7))
_slide_embed_jit = jax.jit(_slide_embed, static_argnums=(3,))


def segment_schedule(sizes):
    """The five log2-spaced segment lengths of the published
    ``get_optimal_segment_length`` for the configuration's ``max_wsi_size``
    and ``tile_size``, or the ``segment_length`` the file states."""
    if sizes.get("segment_length"):
        return tuple(int(s) for s in sizes["segment_length"])
    max_seq = (int(sizes["max_wsi_size"]) // int(sizes["tile_size"])) ** 2
    exps = np.linspace(math.log2(1024), int(math.log2(max_seq)), 5)
    return tuple(int(s) for s in np.power(2, exps).astype(int))


def slide_forward(params, feats, coords, sizes, mode="f32"):
    """One slide: ``feats [N, in_chans]``, ``coords [N, 2]`` -> the class
    token of the embedding and of each layer's output, each through the
    final norm: ``[depth + 1, embed_dim]`` float32 on the host."""
    E, heads = int(sizes["embed_dim"]), int(sizes["num_heads"])
    eps_layer, eps_out = float(sizes["layernorm_eps"]), float(sizes["norm_eps"])
    segs = segment_schedule(sizes)
    ratios = tuple(int(r) for r in sizes["dilated_ratio"])
    pos = sincos_2d(E, coords, int(sizes["tile_size"]), int(sizes["slide_ngrids"]))
    x = _slide_embed_jit(params, jnp.asarray(feats, jnp.float32), jnp.asarray(pos), mode)
    states = [x[0]]
    for i in range(int(sizes["depth"])):
        x = _longnet_layer_jit(params["encoder"][f"layers_{i}"], x, heads, eps_layer,
                               segs, ratios, sizes.get("gelu", "erf"), mode)
        states.append(x[0])
    return np.asarray(_layer_norm_jit(params["norm"], jnp.stack(states), eps_out))
