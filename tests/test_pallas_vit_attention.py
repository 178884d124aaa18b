"""The ViT attention core over packed QKV (``ops/pallas_vit_attention.py``).

The kernel runs here in Pallas interpret mode (the library never picks it:
the tests ask for it) against ``attention_with_lse`` on the same packed
array; the model takes it only where the device gate and the shape gate
both say so, which a test steers by monkeypatching the gate itself.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from gigapath_tpu.models.tile_encoder import ViTAttention, VisionTransformer, init_params
from gigapath_tpu.ops import pallas_vit_attention as pva
from gigapath_tpu.ops.attention import attention_with_lse

VIT_G = dict(B=2, N=197, H=24, hd=64)        # the tile encoder's attention
ONE_WIDE_HEAD = dict(B=2, N=50, H=2, hd=128)  # a head is a whole lane group


def _packed(B, N, H, hd, dtype, seed=0):
    x = jax.random.normal(jax.random.PRNGKey(seed), (B, N, 3 * H * hd), jnp.float32)
    return (1.5 * x).astype(dtype)


def _reference(qkv, H):
    B, N, D3 = qkv.shape
    x = qkv.reshape(B, N, 3, H, D3 // 3 // H)
    out, _ = attention_with_lse(x[:, :, 0], x[:, :, 1], x[:, :, 2])
    return out.reshape(B, N, D3 // 3)


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


# bfloat16: two units in the last place of the output (2**-8 each)
@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5), (jnp.bfloat16, 2**-7)],
                         ids=["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [VIT_G, ONE_WIDE_HEAD, dict(B=1, N=37, H=8, hd=32),
                                   dict(B=1, N=20, H=1, hd=256)],
                         ids=["vit_g", "one_wide_head", "four_heads_a_group", "head_of_256"])
def test_kernel_matches_attention_with_lse(shape, dtype, tol):
    qkv = _packed(dtype=dtype, **shape)
    assert pva.fits(qkv.shape, shape["H"], dtype)
    got = pva.packed_qkv_attention(qkv, shape["H"], interpret=True)
    want = _reference(qkv, shape["H"])
    assert got.shape == want.shape and got.dtype == want.dtype
    np.testing.assert_allclose(_f32(got), _f32(want), atol=tol, rtol=tol)


def test_jnp_form_is_attention_with_lse_on_the_split():
    qkv = _packed(dtype=jnp.float32, **VIT_G)
    np.testing.assert_array_equal(
        _f32(pva.packed_qkv_attention_jnp(qkv, 24)), _f32(_reference(qkv, 24)))


@pytest.mark.parametrize("shape", [VIT_G, ONE_WIDE_HEAD], ids=["vit_g", "one_wide_head"])
def test_grad_is_the_jnp_forms(shape):
    """No backward kernel: the VJP differentiates the jnp form recomputed
    from the saved qkv, so the cotangent is that form's own."""
    H = shape["H"]
    qkv = _packed(dtype=jnp.float32, **shape)
    w = jax.random.normal(jax.random.PRNGKey(7), (shape["B"], shape["N"], H * shape["hd"]))

    def loss(fn):
        return jax.grad(lambda a: (fn(a) * w).sum())(qkv)

    got = loss(lambda a: pva.packed_qkv_attention(a, H, interpret=True))
    want = loss(lambda a: _reference(a, H))
    np.testing.assert_allclose(_f32(got), _f32(want), atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("shape,heads,dtype,takes", [
    ((128, 197, 4608), 24, jnp.bfloat16, True),    # ViT-G/14, the cell
    ((128, 197, 4608), 24, jnp.float32, True),
    ((2, 5, 96), 4, jnp.float32, False),           # vit_tile_enc_test: heads of 8
    ((2, 197, 3 * 192), 3, jnp.bfloat16, False),   # heads of 64, D not a lane multiple
    ((2, 197, 3 * 384), 4, jnp.bfloat16, False),   # heads of 96 do not tile 128 lanes
    ((2, 785, 4608), 24, jnp.bfloat16, False),     # 448-px input: N no longer fits VMEM
    ((2, 197, 4608), 24, jnp.float16, False),
    ((2, 197, 4607), 24, jnp.bfloat16, False),
], ids=["vit_g_bf16", "vit_g_f32", "heads_of_8", "narrow_model", "heads_of_96",
        "long_sequence", "float16", "not_packed"])
def test_shape_gate(shape, heads, dtype, takes):
    assert pva.fits(shape, heads, dtype) is takes


def _kernel_calls(fn, *args) -> int:
    def walk(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            n += eqn.primitive.name == "pallas_call"
            for sub in jax.core.jaxprs_in_params(eqn.params):
                n += walk(sub)
        return n

    return walk(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.fixture
def on_tpu(monkeypatch):
    """The device gate answers "TPU" and every ``pallas_call`` is interpreted."""
    import gigapath_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        yield


def test_module_takes_the_kernel_where_both_gates_say_so(on_tpu, monkeypatch):
    import gigapath_tpu.ops.flash_attention as fa

    attn = ViTAttention(dim=256, num_heads=4)  # heads of 64
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 19, 256))
    params = attn.init(jax.random.PRNGKey(1), x)
    assert _kernel_calls(attn.apply, params, x) == 1
    got = attn.apply(params, x)
    monkeypatch.setattr(fa, "_on_tpu", lambda: False)
    assert _kernel_calls(attn.apply, params, x) == 0
    np.testing.assert_allclose(_f32(got), _f32(attn.apply(params, x)), atol=2e-5, rtol=0)


def test_heads_of_8_fall_to_the_jnp_path_on_tpu(on_tpu):
    from gigapath_tpu.utils.registry import create_model_from_registry

    model = create_model_from_registry("vit_tile_enc_test")
    params = init_params(model)
    x = jnp.zeros((2, model.img_size, model.img_size, 3))
    assert _kernel_calls(lambda p, a: model.apply({"params": p}, a), params, x) == 0


def test_the_quant_attn_rider_keeps_its_own_branch(on_tpu):
    attn = ViTAttention(dim=256, num_heads=4, quant="int8+attn")
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 19, 256))
    params = attn.init(jax.random.PRNGKey(1), x)
    assert _kernel_calls(attn.apply, params, x) == 0


def test_blocks_share_one_trace_and_grad_flows_through_the_model(on_tpu):
    """Two blocks, one traced kernel function; ``jax.grad`` through
    ``VisionTransformer`` on the kernel path equals the jnp path's."""
    import gigapath_tpu.ops.flash_attention as fa

    model = VisionTransformer(img_size=32, patch_size=16, embed_dim=128, depth=2,
                              num_heads=2, mlp_ratio=2.0, init_values=0.5)
    params = init_params(model)
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 32, 3))

    def loss(p):
        return (model.apply({"params": p}, x) ** 2).sum()

    text = jax.jit(loss).lower(params).as_text()
    assert text.count("func.func private @packed_qkv_attention") == 1
    assert text.count("call @packed_qkv_attention") == 2
    got = jax.grad(loss)(params)
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(fa, "_on_tpu", lambda: False)
        want = jax.grad(loss)(params)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(_f32(g), _f32(w), atol=1e-4, rtol=1e-4)
