"""The three entries of ``gigapath_tpu/pipeline.py`` as halves under spans:
``<entry>_to_device`` -> the jitted function (one a model) -> ``<entry>_to_host``,
called by ``<entry>_request`` under ``request`` > ``prepare``, ``h2d``,
``dispatch``, ``device_wait``, ``d2h``. Held here: the halves give what the
entry gave before it was split, to the bit; a recorded request opens exactly
those six names; a second request traces nothing; and neither the spans nor
the recorder reach the jitted program."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from PIL import Image

from gigapath_tpu import pipeline
from gigapath_tpu.obs import spans

SIX = {"request", "prepare", "h2d", "dispatch", "device_wait", "d2h"}
PHASES = {"trace", "lower", "compile"}
KINDS = ("tile", "slide", "granite", "axk1", "dsv32")


def _tile():
    from gigapath_tpu.models.tile_encoder import VisionTransformer, init_params

    model = VisionTransformer(img_size=32, patch_size=16, embed_dim=32, depth=1,
                              num_heads=4, mlp_ratio=2.0)
    imgs = np.random.default_rng(0).standard_normal((3, 32, 32, 3)).astype(np.float32)

    def bare(fn, params):  # the entry's batch body as it stood before the split
        padded = np.concatenate([imgs, np.zeros((1, 32, 32, 3), imgs.dtype)])
        return np.asarray(fn(params, jnp.asarray(padded, jnp.bfloat16))[:3], np.float32)

    return dict(
        model=model, params=init_params(model), fn_of=pipeline.tile_encode_fn,
        module="jit_tile_encode", bare=bare,
        request=lambda fn, params: pipeline.tile_encoder_request(fn, params, imgs, 4),
        halves=lambda fn, params: pipeline.tile_encoder_to_host(
            fn(params, *pipeline.tile_encoder_to_device(imgs, 4)), 3),
        entry=None,
    )


def _slide():
    from gigapath_tpu.models import slide_encoder as slide_lib

    model, params = slide_lib.create_model("", "gigapath_slide_enc_tiny", in_chans=32)
    rng = np.random.default_rng(1)
    feats = rng.standard_normal((40, 32)).astype(np.float32)
    coords = rng.uniform(0, 25000, (40, 2)).astype(np.float32)

    def bare(fn, params):
        outs = fn(params, jnp.asarray(feats)[None].astype(jnp.bfloat16),
                  jnp.asarray(coords, jnp.float32)[None])
        answer = {f"layer_{i}_embed": np.asarray(e, np.float32) for i, e in enumerate(outs)}
        answer["last_layer_embed"] = np.asarray(outs[-1], np.float32)
        return answer

    return dict(
        model=model, params=params, fn_of=pipeline.slide_forward_fn,
        module="jit_slide_forward", bare=bare,
        request=lambda fn, params: pipeline.slide_encoder_request(fn, params, feats, coords),
        halves=lambda fn, params: pipeline.slide_encoder_to_host(
            fn(params, *pipeline.slide_encoder_to_device(feats, coords))),
        entry=lambda model, params: pipeline.run_inference_with_slide_encoder(
            feats, coords, model, params),
    )


def _lm(arch, **sizes):
    from gigapath_tpu.models import granite_hybrid

    model, params = granite_hybrid.create_lm(arch, experts_held=4, vocab_size=128, **sizes)
    ids = (np.arange(40) * 7) % 128
    rows = [3, 39]

    def bare(fn, params):
        positions = np.asarray([rows], np.int32)
        logits, received, *more = fn(
            params, jnp.asarray(ids[None].astype(np.int32)), jnp.asarray(positions))
        return {**{k: np.asarray(v) for extras in more for k, v in extras.items()},
                "logits": np.asarray(logits, np.float32), "positions": positions,
                "expert_tokens": np.asarray(received)}

    def halves(fn, params):
        args, host_rows = pipeline.lm_to_device(ids, rows)
        assert isinstance(host_rows, np.ndarray) and host_rows.flags.writeable
        return pipeline.lm_to_host(fn(params, *args), host_rows)

    return dict(
        model=model, params=params, fn_of=pipeline.lm_forward_fn, module="jit_lm_forward",
        bare=bare, halves=halves,
        request=lambda fn, params: pipeline.lm_request(fn, params, ids, rows),
        entry=lambda model, params: pipeline.run_inference_with_lm(ids, rows, model, params),
    )


_BUILD = {
    "tile": _tile,
    "slide": _slide,
    "granite": lambda: _lm("granite_hybrid_tiny", depth=4),
    "axk1": lambda: _lm("axk1_tiny", depth=3),
    "dsv32": lambda: _lm("deepseek_v32_tiny", depth=3, mtp=1),
}


@pytest.fixture(scope="module", params=KINDS)
def case(request):
    return _BUILD[request.param]()


def _same(a, b):
    if isinstance(a, dict):
        assert a.keys() == b.keys()
        for key in a:
            _same(a[key], b[key])
    else:
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


def test_halves_give_the_entrys_result_to_the_bit(case):
    fn = case["fn_of"](case["model"])
    want = case["bare"](fn, case["params"])
    _same(case["request"](fn, case["params"]), want)
    _same(case["halves"](fn, case["params"]), want)
    if case["entry"] is not None:
        _same(case["entry"](case["model"], case["params"]), want)
    with spans.record():  # fenced spans change no answer
        _same(case["request"](fn, case["params"]), want)


def test_a_recorded_request_opens_exactly_the_six_names(case):
    fn = case["fn_of"].__wrapped__(case["model"])  # a function nothing has traced yet
    with spans.record() as rec:
        case["request"](fn, case["params"])
        case["request"](fn, case["params"])
    first, second = [s for s in rec.spans if s.name == "request"]
    assert first.parent is None and second.parent is None
    beneath = {}
    for root in (first, second):
        children = [s for s in rec.spans if s.parent == root.id]
        assert [s.name for s in sorted(children, key=lambda s: s.start_ns)] == [
            "prepare", "h2d", "dispatch", "device_wait", "d2h"]
        for a, b in zip(children, children[1:]):
            assert a.end_ns <= b.start_ns or b.end_ns <= a.start_ns
        ids = {root.id} | {s.id for s in children}
        beneath[root.id] = [s for s in rec.spans if s.id in ids or s.parent in ids]
    assert {s.name for s in beneath[first.id]} == SIX | PHASES   # the first call compiles
    assert {s.name for s in beneath[second.id]} == SIX           # the second traces nothing
    # the jitted function's own three phases are the first dispatch's children
    dispatch = next(s for s in beneath[first.id] if s.name == "dispatch")
    own = [s for s in rec.spans if s.name in PHASES
           and case["module"][len("jit_"):] in s.fields["fun_name"]]
    assert {s.name for s in own} == PHASES and all(s.parent == dispatch.id for s in own)
    # what lies outside the two requests is a phase paid for under no span, or nothing
    inside = {s.id for under in beneath.values() for s in under}
    assert {s.name for s in rec.spans if s.id not in inside} <= PHASES


def test_entry_keeps_one_function_a_model(case):
    fn = case["fn_of"](case["model"])
    assert case["fn_of"](dataclasses.replace(case["model"])) is fn
    if case["entry"] is None:
        return
    case["entry"](case["model"], case["params"])  # whatever is left to compile, compiled
    with spans.record() as rec:
        case["entry"](case["model"], case["params"])
    assert {s.name for s in rec.spans} == SIX


def test_spans_and_recorder_leave_the_jitted_program_alone(case):
    fn = case["fn_of"].__wrapped__(case["model"])
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), case["params"])
    captured = {}

    def lowering(params, *args):
        captured["text"] = fn.lower(shapes, *args).as_text()
        return fn(params, *args)

    case["request"](lowering, case["params"])
    bare = captured["text"]
    with spans.record():
        case["request"](lowering, case["params"])
    assert captured["text"] == bare
    assert f"module @{case['module']} " in bare


def test_a_list_of_segments_is_one_model_and_one_function():
    """``LongNetViT`` keeps its segments as a tuple whatever sequence it was
    given, so the module hashes and the entry's second call traces nothing."""
    from gigapath_tpu.models import slide_encoder as slide_lib

    model, params = slide_lib.create_model(
        "", "gigapath_slide_enc_tiny", in_chans=32, segment_length=[16, 32])
    assert model.segment_length == (16, 32) and hash(model) == hash(model.clone())
    twin = slide_lib.LongNetViT(**{f.name: getattr(model, f.name)
                                   for f in dataclasses.fields(model) if f.init})
    assert pipeline.slide_forward_fn(model) is pipeline.slide_forward_fn(twin)
    rng = np.random.default_rng(2)
    batch = (rng.standard_normal((20, 32)).astype(np.float32),
             rng.uniform(0, 25000, (20, 2)).astype(np.float32))
    out = pipeline.run_inference_with_slide_encoder(*batch, model, params)
    assert out["last_layer_embed"].shape == (1, 32)
    with spans.record() as rec:
        pipeline.run_inference_with_slide_encoder(*batch, twin, params)
    assert {s.name for s in rec.spans} == SIX


def test_tile_entry_opens_one_request_a_batch(tmp_path):
    from gigapath_tpu.models.tile_encoder import VisionTransformer, init_params

    rng = np.random.default_rng(3)
    paths = []
    for i in range(5):  # 5 tiles, batch 4 -> one full + one partial
        path = tmp_path / f"{i:05d}x_{i:05d}y.png"
        Image.fromarray(rng.integers(0, 255, (32, 32, 3)).astype(np.uint8)).save(path)
        paths.append(str(path))
    model = VisionTransformer(img_size=32, patch_size=16, embed_dim=32, depth=1,
                              num_heads=4, mlp_ratio=2.0)
    with spans.record() as rec:
        out = pipeline.run_inference_with_tile_encoder(paths, model, init_params(model),
                                                       batch_size=4)
    assert out["tile_embeds"].shape == (5, 32)
    roots = [s.name for s in sorted(rec.spans, key=lambda s: s.start_ns) if s.parent is None
             and s.name not in PHASES]
    assert roots == ["request", "request"]  # the dataset reads lie between them, under no span
    assert {s.name for s in rec.spans} - PHASES == SIX
