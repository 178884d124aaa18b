"""``benchmarks/lib/reference.py`` and ``weights.py`` against the program at
the tiny size in float32: the two are written apart and have to agree to
rounding; the lower precisions have to fall away from it in order."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.lib import reference, tables, weights
from benchmarks.drivers.closed_loop import row_gaps


def _tiny(config):
    return tables.load("configs", config)["tiny"]


def _tile(dtype, seed=2147483659):
    from gigapath_tpu import pipeline
    from gigapath_tpu.models import tile_encoder as te

    sizes = _tiny("gigapath_tile_enc")
    model = te.vit_tile_enc_test(dtype=dtype)
    x = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
    params = weights.make_weights(
        jax.eval_shape(model.init, jax.random.PRNGKey(0), x)["params"], seed)
    imgs = np.random.default_rng(seed).standard_normal((6, 32, 32, 3), dtype=np.float32)
    out = pipeline.tile_encode_fn(model)(params, jnp.asarray(imgs, dtype or jnp.float32))
    return sizes, params, imgs, np.asarray(out, np.float32)


def _slide(dtype, seed=2147483659, n=77):
    from gigapath_tpu import pipeline
    from gigapath_tpu.models import slide_encoder as se

    sizes = _tiny("gigapath_slide_enc12l768d")
    model = se.gigapath_slide_enc_tiny(in_chans=sizes["in_chans"], dtype=dtype)
    x = jax.ShapeDtypeStruct((1, 4, sizes["in_chans"]), jnp.float32)
    c = jax.ShapeDtypeStruct((1, 4, 2), jnp.float32)
    params = weights.make_weights(
        jax.eval_shape(model.init, jax.random.PRNGKey(0), x, c)["params"], seed)
    rng = np.random.default_rng(seed)
    feats = rng.standard_normal((2, n, sizes["in_chans"]), dtype=np.float32)
    coords = rng.uniform(0, 250000, (2, n, 2)).astype(np.float32)
    outs = pipeline.slide_forward_fn(model)(
        params, jnp.asarray(feats, dtype or jnp.float32), jnp.asarray(coords))
    return sizes, params, feats, coords, np.stack([np.asarray(o, np.float32) for o in outs], 1)


def test_vit_reference_agrees_with_the_program_in_float32():
    sizes, params, imgs, out = _tile(None)
    ref = reference.vit_forward(params, imgs, sizes, "f32", block_rows=4)
    assert row_gaps(out, ref).max() < 1e-5


@pytest.mark.parametrize("n", [77, 33])
def test_slide_reference_agrees_with_the_program_in_float32(n):
    """To rounding with the program's own GELU (flax's tanh form); the
    published erf form, which the configuration states, lies 1e-3 away."""
    sizes, params, feats, coords, out = _slide(None, n=n)
    for gelu, tol in (("tanh", 1e-5), ("erf", 2e-3)):
        ref = np.stack([
            reference.slide_forward(params, feats[b], coords[b], dict(sizes, gelu=gelu))
            for b in range(2)])
        assert ref.shape == out.shape == (2, sizes["depth"] + 1, sizes["embed_dim"])
        assert row_gaps(out, ref).max() < tol


@pytest.mark.parametrize("which", ["tile", "slide"])
def test_lower_precisions_fall_away_in_order(which):
    if which == "tile":
        sizes, params, imgs, _ = _tile(None)
        run = lambda mode: reference.vit_forward(params, imgs, sizes, mode)  # noqa: E731
    else:
        sizes, params, feats, coords, _ = _slide(None)
        run = lambda mode: reference.slide_forward(params, feats[0], coords[0], sizes, mode)  # noqa: E731
    exact = run("f32")
    gap = {mode: row_gaps(run(mode), exact).mean() for mode in ("bf16", "int8", "fp8")}
    assert 0 < gap["bf16"] < gap["int8"] < gap["fp8"]
    assert gap["fp8"] > 3 * gap["bf16"]
    with pytest.raises(ValueError, match="unknown precision"):
        run("fp4")


def test_dilated_attention_reference_against_a_mask_oracle():
    """Every (position, head) of a branch attends exactly the positions of
    its own segment that share its phase."""
    L, H, D = 23, 4, 8
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((L, H, D)), jnp.float32) for _ in range(3))
    segs, ratios = (8, 16), (1, 2)
    got = np.asarray(reference.dilated_attention(q, k, v, segs, ratios, "f32"))
    outs, lses = [], []
    for s, r in zip(segs, ratios):
        o = np.zeros((L, H, D)); l = np.full((L, H), -np.inf)
        for h in range(H):
            phase = h // -(-H // r)
            for i in range(L):
                if (i % s) % r != phase:
                    continue
                keys = [j for j in range(L) if j // s == i // s and (j % s) % r == phase]
                logits = np.asarray(k)[keys, h] @ np.asarray(q)[i, h] / np.sqrt(D)
                l[i, h] = np.log(np.exp(logits - logits.max()).sum()) + logits.max()
                o[i, h] = np.exp(logits - l[i, h]) @ np.asarray(v)[keys, h]
        outs.append(o); lses.append(l)
    w = np.exp(np.stack(lses) - np.max(np.stack(lses), axis=0))
    w = w / w.sum(0)
    want = sum(o * wi[..., None] for o, wi in zip(outs, w))
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


def test_weights_come_from_the_seed_alone():
    shapes = {"a": {"kernel": jax.ShapeDtypeStruct((16, 8), jnp.float32),
                    "bias": jax.ShapeDtypeStruct((8,), jnp.float32)},
              "ls": {"gamma": jax.ShapeDtypeStruct((8,), jnp.float32)},
              "norm": {"scale": jax.ShapeDtypeStruct((8,), jnp.float32)},
              "cls_token": jax.ShapeDtypeStruct((1, 1, 8), jnp.bfloat16)}
    big = 2**31 + 12345
    one, two, other = (weights.make_weights(shapes, s) for s in (big, big, big - 2**31))
    for x, y in zip(jax.tree.leaves(one), jax.tree.leaves(two)):
        np.testing.assert_array_equal(np.asarray(x, np.float32), np.asarray(y, np.float32))
    assert not np.array_equal(np.asarray(one["a"]["kernel"]), np.asarray(other["a"]["kernel"]))
    assert one["cls_token"].dtype == jnp.bfloat16
    assert 0.1 <= float(one["ls"]["gamma"].min()) and float(one["ls"]["gamma"].max()) <= 0.3
    assert abs(float(one["norm"]["scale"].mean()) - 1.0) < 0.2
    assert float(jnp.abs(one["a"]["kernel"]).max()) < 2.0
