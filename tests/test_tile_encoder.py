"""Tile encoder (flax ViT-G/14) tests.

Oracle strategy: the reference consumes the tile encoder through timm
(``gigapath/pipeline.py:126-128``); timm is not in this environment, so the
oracle is a hand-written torch-functional forward implementing the timm
DINOv2 block math (conv patch embed, packed qkv, LayerScale, SwiGLU) from a
timm-named state dict. The converter + flax model must reproduce it exactly.

The golden-tile parity test (reference ``demo/3_load_tile_encoder.py:28-34``,
atol 1e-2 vs ``images/prov_normal_000_1.pt``) additionally needs the real
1.13 B-param pretrained checkpoint, which is not available in the zero-egress
environment — it runs whenever ``GIGAPATH_TILE_ENCODER_CKPT`` points at one.
"""

import collections.abc
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import torch
import torch.nn.functional as F

from gigapath_tpu.models.tile_encoder import (
    VisionTransformer,
    convert_timm_state_dict,
    count_params,
    create_tile_encoder,
    init_params,
    interpolate_pos_embed,
)
from gigapath_tpu.utils.torch_convert import merge_into_params

TINY = dict(
    img_size=32, patch_size=16, embed_dim=32, depth=2, num_heads=4,
    mlp_ratio=4.0, swiglu=True, init_values=1e-5,
)


def make_timm_state_dict(cfg, seed=0):
    """Random timm-named ViT state dict for the given config."""
    g = torch.Generator().manual_seed(seed)
    D, depth = cfg["embed_dim"], cfg["depth"]
    p = cfg["patch_size"]
    n_tok = (cfg["img_size"] // p) ** 2 + 1
    hidden = int(D * cfg["mlp_ratio"])
    fc2_in = hidden // 2 if cfg["swiglu"] else hidden

    def t(*shape):
        return torch.randn(*shape, generator=g) * 0.05

    sd = {
        "cls_token": t(1, 1, D),
        "pos_embed": t(1, n_tok, D),
        "patch_embed.proj.weight": t(D, 3, p, p),
        "patch_embed.proj.bias": t(D),
        "norm.weight": 1.0 + t(D),
        "norm.bias": t(D),
    }
    for i in range(depth):
        b = f"blocks.{i}."
        sd.update(
            {
                b + "norm1.weight": 1.0 + t(D),
                b + "norm1.bias": t(D),
                b + "attn.qkv.weight": t(3 * D, D),
                b + "attn.qkv.bias": t(3 * D),
                b + "attn.proj.weight": t(D, D),
                b + "attn.proj.bias": t(D),
                b + "ls1.gamma": t(D),
                b + "norm2.weight": 1.0 + t(D),
                b + "norm2.bias": t(D),
                b + "mlp.fc1.weight": t(hidden, D),
                b + "mlp.fc1.bias": t(hidden),
                b + "mlp.fc2.weight": t(D, fc2_in),
                b + "mlp.fc2.bias": t(D),
                b + "ls2.gamma": t(D),
            }
        )
    return sd


def torch_vit_forward(sd, x, cfg):
    """timm DINOv2 ViT forward in plain torch functional ops (the oracle)."""
    D, H = cfg["embed_dim"], cfg["num_heads"]
    depth, p = cfg["depth"], cfg["patch_size"]
    hd = D // H
    eps = 1e-6
    B = x.shape[0]

    x = F.conv2d(x, sd["patch_embed.proj.weight"], sd["patch_embed.proj.bias"], stride=p)
    x = x.flatten(2).transpose(1, 2)  # [B, N, D]
    cls = sd["cls_token"].expand(B, -1, -1)
    x = torch.cat([cls, x], dim=1) + sd["pos_embed"]
    N = x.shape[1]

    for i in range(depth):
        b = f"blocks.{i}."
        h = F.layer_norm(x, (D,), sd[b + "norm1.weight"], sd[b + "norm1.bias"], eps)
        qkv = F.linear(h, sd[b + "attn.qkv.weight"], sd[b + "attn.qkv.bias"])
        qkv = qkv.reshape(B, N, 3, H, hd).permute(2, 0, 3, 1, 4)
        q, k, v = qkv.unbind(0)
        attn = (q * hd**-0.5) @ k.transpose(-2, -1)
        attn = attn.softmax(dim=-1)
        h = (attn @ v).transpose(1, 2).reshape(B, N, D)
        h = F.linear(h, sd[b + "attn.proj.weight"], sd[b + "attn.proj.bias"])
        x = x + h * sd[b + "ls1.gamma"]

        h = F.layer_norm(x, (D,), sd[b + "norm2.weight"], sd[b + "norm2.bias"], eps)
        h = F.linear(h, sd[b + "mlp.fc1.weight"], sd[b + "mlp.fc1.bias"])
        if cfg["swiglu"]:
            h1, h2 = h.chunk(2, dim=-1)
            h = F.silu(h1) * h2
        else:
            h = F.gelu(h)
        h = F.linear(h, sd[b + "mlp.fc2.weight"], sd[b + "mlp.fc2.bias"])
        x = x + h * sd[b + "ls2.gamma"]

    x = F.layer_norm(x, (D,), sd["norm.weight"], sd["norm.bias"], eps)
    return x[:, 0]


@pytest.mark.parametrize("swiglu", [True, False])
def test_forward_matches_torch_oracle(swiglu):
    cfg = dict(TINY, swiglu=swiglu)
    sd = make_timm_state_dict(cfg)
    model = VisionTransformer(**cfg)
    params = init_params(model)
    converted = convert_timm_state_dict(sd)
    params, missing, unexpected = merge_into_params(params, converted)
    assert missing == [], missing
    assert unexpected == [], unexpected

    rng = np.random.default_rng(0)
    img = rng.normal(size=(2, 32, 32, 3)).astype(np.float32)
    out = model.apply({"params": params}, jnp.asarray(img))
    ref = torch_vit_forward(sd, torch.from_numpy(img).permute(0, 3, 1, 2), cfg)
    np.testing.assert_allclose(np.asarray(out), ref.numpy(), atol=1e-5, rtol=1e-5)


def test_forward_features_tokens():
    model = VisionTransformer(**TINY)
    params = init_params(model)
    x = jnp.zeros((1, 32, 32, 3))
    tokens = model.apply({"params": params}, x, method=model.forward_features)
    assert tokens.shape == (1, 1 + 4, 32)


def test_gigapath_param_count():
    """The printed reference count (gigapath/pipeline.py:129): 1.13 B."""
    from gigapath_tpu.models.tile_encoder import gigapath_tile_enc

    n = count_params(gigapath_tile_enc())
    assert n == 1_134_953_984, n


def test_pos_embed_interpolation_shapes_and_identity():
    D = 8
    table = np.random.default_rng(0).normal(size=(1, 1 + 16, D)).astype(np.float32)
    same = interpolate_pos_embed(table, 4)
    np.testing.assert_array_equal(same, table)
    up = interpolate_pos_embed(table, 8)
    assert up.shape == (1, 1 + 64, D)
    # cls row untouched
    np.testing.assert_array_equal(up[:, 0], table[:, 0])


def test_create_tile_encoder_checkpoint_roundtrip(tmp_path):
    cfg = TINY
    sd = make_timm_state_dict(cfg, seed=3)
    path = tmp_path / "tile_encoder.pth"
    torch.save(sd, path)
    model, params = create_tile_encoder(str(path), "vit_tile_enc_test")
    rng = np.random.default_rng(1)
    img = rng.normal(size=(1, 32, 32, 3)).astype(np.float32)
    out = model.apply({"params": params}, jnp.asarray(img))
    ref = torch_vit_forward(sd, torch.from_numpy(img).permute(0, 3, 1, 2), cfg)
    np.testing.assert_allclose(np.asarray(out), ref.numpy(), atol=1e-5, rtol=1e-5)


def test_pos_embed_resize_on_grid_mismatch(tmp_path):
    """A checkpoint trained at a different grid loads via interpolation."""
    cfg = dict(TINY, img_size=64)  # grid 4 target
    sd = make_timm_state_dict(TINY)  # grid 2 checkpoint
    converted = convert_timm_state_dict(sd, target_grid=4)
    model = VisionTransformer(**cfg)
    params = init_params(model)
    params, missing, unexpected = merge_into_params(params, converted)
    assert missing == [] and unexpected == []


def test_vendored_timm_key_schema_maps_bijectively():
    """The full-size ViT-G timm key schema (vendored fixture, names+shapes
    only — regenerate with scripts/gen_timm_fixture.py) maps one-to-one onto
    the flax param tree with exact shapes, covering every parameter.

    This is the strongest converter evidence available in a zero-egress
    environment; the weights-level golden check is ``test_golden_tile_parity``
    below (README "Verifying tile-encoder parity").
    """
    import json

    fixture = os.path.join(os.path.dirname(__file__), "fixtures", "timm_vitg_keys.json")
    with open(fixture) as f:
        schema = {k: tuple(v) for k, v in json.load(f).items()}

    # param count of the schema == the derived timm model size
    assert sum(int(np.prod(s)) for s in schema.values()) == 1_134_953_984

    from gigapath_tpu.models.tile_encoder import gigapath_tile_enc

    model = gigapath_tile_enc()
    x = jax.ShapeDtypeStruct((1, 224, 224, 3), jnp.float32)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), x)["params"]
    flat = {
        tuple(getattr(p, "key", str(p)) for p in path): leaf.shape
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }

    # stream keys one at a time (full zero tensors would cost ~4.5 GB)
    converted: dict = {}
    for key, shape in schema.items():
        (path, arr), = convert_timm_state_dict(
            {key: np.zeros(shape, np.float32)}
        ).items()
        assert path not in converted, f"{key} collides at {path}"
        converted[path] = arr.shape

    assert set(converted) == set(flat), (
        sorted(set(flat) - set(converted))[:5],
        sorted(set(converted) - set(flat))[:5],
    )
    for path, shape in converted.items():
        assert tuple(flat[path]) == tuple(shape), (path, flat[path], shape)


class _RecordingEnviron(collections.abc.MutableMapping):
    """``os.environ`` with the names read through it written down."""

    def __init__(self, real):
        self.real, self.reads = real, []

    def __getitem__(self, name):
        self.reads.append(name)
        return self.real[name]

    def __setitem__(self, name, value):
        self.real[name] = value

    def __delitem__(self, name):
        del self.real[name]

    def __iter__(self):
        return iter(self.real)

    def __len__(self):
        return len(self.real)


class TestQuantTierRead:
    """``create_tile_encoder`` is the one reader of ``GIGAPATH_QUANT_TILE`` /
    ``GIGAPATH_QUANT_PALLAS``: once, host side, when neither kwarg is given."""

    @pytest.fixture
    def quant_reads(self, monkeypatch):
        for name in ("GIGAPATH_QUANT_TILE", "GIGAPATH_QUANT_PALLAS"):
            monkeypatch.delenv(name, raising=False)
        env = _RecordingEnviron(os.environ)
        monkeypatch.setattr(os, "environ", env)

        def during(**kwargs):
            """The quant names read while one factory call runs."""
            start = len(env.reads)
            create_tile_encoder("", "vit_tile_enc_test", **kwargs)
            return [n for n in env.reads[start:] if n.startswith("GIGAPATH_QUANT")]

        return during

    def test_env_picks_the_tier(self, quant_reads, monkeypatch):
        monkeypatch.setenv("GIGAPATH_QUANT_TILE", "1")  # an alias of int8
        monkeypatch.setenv("GIGAPATH_QUANT_PALLAS", "1")
        model, _ = create_tile_encoder("", "vit_tile_enc_test")
        assert model.quant == "int8" and model.quant_pallas

    def test_explicit_kwarg_pins_tier(self, quant_reads, monkeypatch):
        monkeypatch.setenv("GIGAPATH_QUANT_TILE", "int8")
        model, _ = create_tile_encoder("", "vit_tile_enc_test", quant="")
        assert model.quant == ""

    def test_no_env_no_kwarg_is_f32_oracle(self, quant_reads):
        model, _ = create_tile_encoder("", "vit_tile_enc_test")
        assert model.quant == "" and not model.quant_pallas

    def test_one_read_per_construction_none_with_a_kwarg(self, quant_reads,
                                                         monkeypatch):
        assert quant_reads() == ["GIGAPATH_QUANT_TILE"]
        monkeypatch.setenv("GIGAPATH_QUANT_TILE", "int8")
        assert quant_reads() == ["GIGAPATH_QUANT_TILE", "GIGAPATH_QUANT_PALLAS"]
        assert quant_reads(quant_pallas=False) == []  # an explicit kwarg: no read


GOLDEN_CKPT = os.environ.get("GIGAPATH_TILE_ENCODER_CKPT", "")
GOLDEN_PNG = "/root/reference/images/prov_normal_000_1.png"
GOLDEN_PT = "/root/reference/images/prov_normal_000_1.pt"


@pytest.mark.skipif(
    not (GOLDEN_CKPT and os.path.exists(GOLDEN_CKPT) and os.path.exists(GOLDEN_PT)),
    reason="pretrained ViT-G checkpoint not available (zero-egress environment)",
)
def test_golden_tile_parity():
    """Reference demo/3_load_tile_encoder.py:28-34: atol 1e-2 vs golden."""
    from PIL import Image

    from gigapath_tpu.data.transforms import preprocess_tile

    model, params = create_tile_encoder(GOLDEN_CKPT, "gigapath_tile_enc")
    img = preprocess_tile(Image.open(GOLDEN_PNG))
    out = model.apply({"params": params}, jnp.asarray(img)[None])
    golden = torch.load(GOLDEN_PT, map_location="cpu", weights_only=True).numpy()
    np.testing.assert_allclose(np.asarray(out), golden, atol=1e-2)
