"""The dropless expert layer's two row kernels (ops/moe/pallas_rows.py) against
the ``jnp`` forms they stand in for, in interpret mode: what is moved is
bounded by ``total``, the rows a held expert owns; what lies past it is never
read as a number."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from gigapath_tpu.ops.moe import DroplessMoE, topk_softmax_gating
from gigapath_tpu.ops.moe import pallas_rows as pr
from gigapath_tpu.ops.moe.expert_parallel import dispatch_to_held, grouped_matmul
from gigapath_tpu.utils.profiling import collect_moe_metadata

S, M, E, K = 512, 256, 8, 2  # 1,024 sorted rows: four dispatch tiles, one combine tile


def _routing(case, held, offset=0, S=S, E=E, k=K, seed=5):
    """(x, weights, experts, order, position, group_sizes) of one of the cases of
    test_granite_hybrid.test_dropless_topk_matches_a_per_token_loop."""
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((S, M)).astype(np.float32)
    logits = rng.standard_normal((S, E)).astype(np.float32)
    if case == "one_expert_gets_none":
        logits[:, 3] = -1e4
    if case == "one_expert_gets_all":
        logits[:, 5] = 1e4
    weights, experts = topk_softmax_gating(jnp.asarray(logits), k)
    order, position, sizes = dispatch_to_held(experts, expert_offset=offset, experts_held=held)
    return jnp.asarray(x), weights, experts, order, position, sizes


def _written(total):
    """Rows the dispatch kernel writes: whole tiles up to the one that holds ``total - 1``."""
    return -(-int(total) // pr.DISPATCH_ROWS) * pr.DISPATCH_ROWS


@jax.jit
def _ascending_sum(out_rows, position, weights, total):
    """The combine's formula, a choice at a time in ascending ``j`` in
    float32: a held choice adds ``row * w``, one that is not held leaves the
    sum as it is (selecting 0 for it would add an exact 0). Evaluated by XLA,
    as the kernel's interpreter is: XLA's CPU backend fuses the product into
    the add (one rounding), which a numpy float32 loop would not, so the
    kernel is held to this and not to numpy."""
    acc = jnp.zeros((position.shape[0], out_rows.shape[1]), jnp.float32)
    for j in range(position.shape[1]):
        row = out_rows[position[:, j]].astype(jnp.float32)
        acc = jnp.where((position[:, j] < total)[:, None], acc + row * weights[:, j, None], acc)
    return acc.astype(out_rows.dtype)


@pytest.mark.parametrize("held,offset", [(8, 0), (4, 0), (4, 4), (1, 5)],
                         ids=["all", "half", "other_half", "one"])
@pytest.mark.parametrize("case", ["spread", "one_expert_gets_none", "one_expert_gets_all"])
def test_kernels_match_the_jnp_form(case, held, offset):
    x, weights, _, order, position, sizes = _routing(case, held, offset)
    total = sizes.sum()
    rows = pr.dispatch_rows_kernel(x, order // K, total, True)
    want_rows = x[order // K]
    n = _written(total)
    assert np.array_equal(np.asarray(rows[:n]), np.asarray(want_rows[:n]))
    # what the grouped products would leave: rows past total hold anything
    out_rows = jnp.where((jnp.arange(S * K) < total)[:, None], want_rows * 0.5, jnp.nan)
    got = pr.combine_rows_kernel(out_rows, position, weights, total, True)
    want = pr.combine_rows_jnp(out_rows, position, weights, total)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got, _ascending_sum(out_rows, position, weights, total))
    if held == E:
        assert int(total) == S * K  # every tile runs: nothing is skipped


@pytest.mark.parametrize("total", [0, 1, 255, 256, 257, 300, 512, 1023, 1024],
                         ids=lambda t: f"total_{t}")
def test_total_inside_a_tile_on_an_edge_and_zero(total):
    """``total`` is a value in the input: the same kernels for each, decided
    tile by tile. Rows of a tile that starts at or past it keep what the
    buffer held (here: whatever the interpreter left), and add nothing."""
    rng = np.random.default_rng(total)
    x = jnp.asarray(rng.standard_normal((S, M)), jnp.float32)
    order = jnp.asarray(rng.permutation(S * K), jnp.int32)
    position = jnp.argsort(order).reshape(S, K)
    weights = jnp.asarray(rng.random((S, K)), jnp.float32)
    total = jnp.int32(total)
    rows = pr.dispatch_rows_kernel(x, order // K, total, True)
    n = _written(total)
    assert np.array_equal(np.asarray(rows[:n]), np.asarray(x[order // K][:n]))
    out_rows = jnp.where((jnp.arange(S * K) < total)[:, None], x[order // K], jnp.nan)
    got = pr.combine_rows_kernel(out_rows, position, weights, total, True)
    np.testing.assert_allclose(got, pr.combine_rows_jnp(out_rows, position, weights, total),
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(got, _ascending_sum(out_rows, position, weights, total))
    if int(total) == 0:
        assert not np.asarray(got).any()


@pytest.mark.parametrize("dtype,k", [(jnp.bfloat16, 4), (jnp.bfloat16, 10), (jnp.float32, 1),
                                     (jnp.bfloat16, 8)],
                         ids=["bf16_top4", "bf16_top10", "f32_top1", "bf16_top8"])
def test_kernels_take_other_widths_of_choice_and_type(dtype, k):
    """top-10 pads a token's choices to 16 in the index blocks (64 tokens a
    combine tile), top-8 fills its 8 with no pad slot; the sum is float32
    whatever the rows' type."""
    s, e = 1024, 16
    rng = np.random.default_rng(k)
    x = jnp.asarray(rng.standard_normal((s, M)), dtype)
    weights, experts = topk_softmax_gating(jnp.asarray(rng.standard_normal((s, e)), jnp.float32), k)
    order, position, sizes = dispatch_to_held(experts, expert_offset=0, experts_held=e // 2)
    total = sizes.sum()
    assert pr.fits(s, M, k, dtype)
    rows = pr.dispatch_rows_kernel(x, order // k, total, True)
    n = _written(total)
    assert np.array_equal(np.asarray(rows[:n], np.float32),
                          np.asarray(x[order // k][:n], np.float32))
    out_rows = jnp.where((jnp.arange(s * k) < total)[:, None], x[order // k], jnp.nan)
    got = pr.combine_rows_kernel(out_rows, position, weights, total, True)
    want = pr.combine_rows_jnp(out_rows, position, weights, total)
    tol = 1e-6 if dtype == jnp.float32 else 2 ** -7
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=tol, atol=tol)
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(
        _ascending_sum(out_rows, position, weights, total), np.float32))


def _held_where(s, k, hold, seed=0):
    """``position [s, k]``: a permutation of the sorted buffer in which the
    choices ``hold [s, k]`` (bool) take the first rows. Returns it and ``total``."""
    rng = np.random.default_rng(seed)
    flat = hold.reshape(-1)
    position = np.empty(s * k, np.int32)
    position[flat] = rng.permutation(int(flat.sum()))
    position[~flat] = int(flat.sum()) + rng.permutation(int((~flat).sum()))
    return jnp.asarray(position.reshape(s, k)), jnp.int32(flat.sum())


@pytest.mark.parametrize("k", [10, 8], ids=["top10", "top8"])
def test_tokens_that_hold_every_choice_or_none_and_a_tile_that_holds_none(k):
    """A token whose ``k`` choices are all held, one with none (its row of
    the output is zeros), and a whole combine tile whose tokens hold nothing
    (its copy loop issues no copy, its sum loop writes zeros). The
    interpreter lands a copy only when a wait covers it and fills what never
    landed with NaN, so a wait short of the rows issued shows in the sum."""
    s = 1024
    tokens = pr.INDEX_BLOCK // pr._padded_k(k)
    rng = np.random.default_rng(k)
    hold = rng.random((s, k)) < 0.5
    hold[0], hold[1], hold[2 * tokens:3 * tokens] = True, False, False
    position, total = _held_where(s, k, hold, seed=k)
    weights = jnp.asarray(rng.random((s, k)), jnp.float32)
    x = jnp.asarray(rng.standard_normal((s * k, M)), jnp.bfloat16)
    out_rows = jnp.where((jnp.arange(s * k) < total)[:, None], x, jnp.nan)
    on_wait = pltpu.InterpretParams(dma_execution_mode="on_wait")
    got = np.asarray(pr.combine_rows_kernel(out_rows, position, weights, total, on_wait),
                     np.float32)
    np.testing.assert_array_equal(got, np.asarray(
        _ascending_sum(out_rows, position, weights, total), np.float32))
    assert got[0].any() and not got[1].any() and not got[2 * tokens:3 * tokens].any()


@pytest.mark.parametrize("k", [1, 2, 8, 10])
@pytest.mark.parametrize("share", [0.0, 0.06, 0.5, 1.0])
def test_each_tokens_choices_are_ordered_held_first(k, share):
    """``_held_first``: a token's held choices (``position < total``) first,
    in ascending ``j``, then ``S * k`` with weight 0 up to ``_padded_k(k)``
    slots; ``counts`` is the held choices a token."""
    s, kp = 256, pr._padded_k(k)
    rng = np.random.default_rng(k)
    position, total = _held_where(s, k, rng.random((s, k)) < share, seed=k)
    weights = jnp.asarray(rng.random((s, k)) + 0.5, jnp.float32)
    got_p, got_w, counts = (np.asarray(a) for a in pr._held_first(position, weights, total, s * k))
    p, w, t = np.asarray(position), np.asarray(weights), int(total)
    assert got_p.shape == got_w.shape == (s, kp) and counts.dtype == np.int32
    np.testing.assert_array_equal(counts, (p < t).sum(1))
    for i in range(s):
        held = [j for j in range(k) if p[i, j] < t]
        n = len(held)
        assert got_p[i, :n].tolist() == p[i, held].tolist()
        assert got_w[i, :n].tolist() == w[i, held].tolist()
        assert (got_p[i, n:] == s * k).all() and (got_w[i, n:] == 0).all()


@pytest.mark.parametrize("total", [S * K, S * K + 64], ids=["total_all", "total_past_the_buffer"])
def test_a_position_outside_the_buffer_is_never_read(total):
    """The kernel's copies go unchecked by the compiler, so a row index past
    the sorted buffer or below 0 must never reach one: such a choice is not
    held and adds 0, whatever ``total`` says. The interpreter raises on a
    read outside an array."""
    rng = np.random.default_rng(total)
    position = np.asarray(rng.permutation(S * K), np.int32).reshape(S, K)
    position[0, 0], position[1, 1], position[2] = S * K + 3, -2, (-1, S * K)
    weights = jnp.asarray(rng.random((S, K)), jnp.float32)
    out_rows = jnp.asarray(rng.standard_normal((S * K, M)), jnp.float32)
    got = pr.combine_rows_kernel(out_rows, jnp.asarray(position), weights, jnp.int32(total),
                                 pltpu.InterpretParams())
    inside = np.where((position >= 0) & (position < S * K), position, S * K)
    np.testing.assert_array_equal(got, _ascending_sum(out_rows, jnp.asarray(inside), weights,
                                                      jnp.int32(S * K)))
    assert not np.asarray(got[2]).any()


def test_the_combine_orders_its_choices_without_a_sort():
    """What the combine traces to at a Granite-like shape: the ordering is
    compares, selects and sums; no sort, scatter or gather is left to XLA."""
    s, m, k = 16384, 4096, 10
    args = (jax.ShapeDtypeStruct((s * k, m), jnp.bfloat16), jax.ShapeDtypeStruct((s, k), jnp.int32),
            jax.ShapeDtypeStruct((s, k), jnp.float32), jax.ShapeDtypeStruct((), jnp.int32))
    jaxpr = jax.make_jaxpr(pr.combine_rows_kernel)(*args)
    names = set()

    def walk(j):
        for eqn in j.eqns:
            names.add(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)
    walk(jaxpr.jaxpr)
    assert "pallas_call" in names
    assert not names & {"sort", "scatter", "scatter-add", "scatter_add", "gather", "top_k",
                        "cumsum", "argsort"}, names


@pytest.mark.parametrize("held", [4, 1], ids=["half", "one"])
def test_a_select_not_a_product_with_zero(held):
    """The layer's pieces in order, the sorted buffer poisoned with NaN past
    ``total`` before the grouped products (what a buffer nobody wrote may
    hold): a matrix product's rows are independent, so the tile that straddles
    ``total`` gives the held rows the same numbers beside NaN neighbours, and
    the combine selects, so the output is finite and equal."""
    x, weights, _, order, position, sizes = _routing("spread", held)
    total = sizes.sum()
    rng = np.random.default_rng(held)
    w1 = jnp.asarray(rng.standard_normal((held, M, 64)) / 16, jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((held, 32, M)) / 8, jnp.float32)
    past = (jnp.arange(S * K) >= total)[:, None]

    def experts_of(rows):
        a, b = jnp.split(grouped_matmul(rows, w1, sizes), 2, axis=-1)
        return grouped_matmul(jax.nn.silu(a) * b, w2, sizes)

    clean = experts_of(x[order // K])
    rows = pr.dispatch_rows_kernel(x, order // K, total, True)
    poisoned = experts_of(jnp.where(past, jnp.nan, rows))
    t = int(total)
    assert 0 < t < S * K and t % pr.DISPATCH_ROWS  # a tile straddles it
    assert np.array_equal(np.asarray(poisoned[:t]), np.asarray(clean[:t]))
    poisoned = jnp.where(past, jnp.nan, poisoned)  # as gmm leaves rows it never visits
    got = pr.combine_rows_kernel(poisoned, position, weights, total, True)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, pr.combine_rows_jnp(clean, position, weights, total),
                               rtol=1e-6, atol=1e-6)
    # the product with a zero weight, for contrast, is not finite
    assert not np.isfinite(np.asarray(
        (poisoned[position] * jnp.where(position < total, weights, 0)[..., None]).sum(1))).all()


def _tier(monkeypatch, on_tpu, s, m, k, dtype=jnp.float32):
    """Which primitives the two seams trace to at this platform and shape."""
    import gigapath_tpu.ops.flash_attention as fa

    monkeypatch.setattr(fa, "_on_tpu", lambda: on_tpu)
    x = jax.ShapeDtypeStruct((s, m), dtype)
    order = jax.ShapeDtypeStruct((s * k,), jnp.int32)
    rows = jax.ShapeDtypeStruct((s * k, m), dtype)
    ik = jax.ShapeDtypeStruct((s, k), jnp.int32)
    fk = jax.ShapeDtypeStruct((s, k), jnp.float32)
    n = jax.ShapeDtypeStruct((), jnp.int32)
    with pltpu.force_tpu_interpret_mode():  # fresh functions: a trace is cached by function
        return (str(jax.make_jaxpr(lambda x, o, n: pr.dispatch_rows(x, o, n, k))(x, order, n)),
                str(jax.make_jaxpr(lambda *a: pr.combine_rows(*a))(rows, ik, fk, n)))


@pytest.mark.parametrize("on_tpu,m,dtype,kernel", [
    (True, 256, jnp.float32, True), (True, 256, jnp.bfloat16, True),
    (False, 256, jnp.float32, False),   # off a TPU: the jnp form
    (True, 96, jnp.float32, False),     # not whole 128-lane lines
    (True, 128, jnp.bfloat16, False),   # a 2-byte row needs an even number of lines
    (True, 128, jnp.float32, True),
    (True, 256, jnp.float16, False),
], ids=["tpu_f32", "tpu_bf16", "cpu", "m96", "m128_bf16", "m128_f32", "f16"])
def test_the_tier_is_chosen_by_platform_and_shape(monkeypatch, on_tpu, m, dtype, kernel):
    for text in _tier(monkeypatch, on_tpu, S, m, K, dtype):
        assert ("pallas_call" in text) == kernel
    assert pr.fits(S, m, K, dtype) == (kernel or not on_tpu)


@pytest.mark.parametrize("s,k,ok", [(512, 2, True), (500, 2, False), (256, 2, False),
                                    (1024, 10, True), (16384, 10, True), (96, 10, False)])
def test_the_shape_gate_wants_whole_tiles_of_rows_and_tokens(s, k, ok):
    assert pr.fits(s, 4096, k, jnp.bfloat16) == ok


def test_the_shape_gate_counts_the_fast_memory_it_asks_for():
    assert pr.fits(16384, 4096, 10, jnp.bfloat16)
    assert not pr.fits(16384, 32768, 10, jnp.float32)


@pytest.mark.parametrize("which", ["dispatch", "combine"])
def test_the_kernel_tier_refuses_a_gradient_with_a_message(which):
    x, weights, _, order, position, sizes = _routing("spread", 4)
    total = sizes.sum()
    if which == "dispatch":
        loss = lambda x: pr.dispatch_rows_kernel(x, order // K, total, True).sum()
    else:
        rows = x[order // K]
        loss = lambda w: pr.combine_rows_kernel(rows, position, w, total, True).sum()
    with pytest.raises(NotImplementedError, match="forward only"):
        jax.grad(loss)(x if which == "dispatch" else weights)


@pytest.mark.parametrize("held,share", [(8, 1.0), (4, None), (1, None)], ids=["all", "half", "one"])
def test_the_layer_counts_the_share_of_the_buffer_it_touches(held, share):
    layer = DroplessMoE(16, 8, E, K, experts_held=held, dtype=jnp.float32,
                        param_dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((40, 16)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    (_, received), state = layer.apply({"params": params}, x, mutable=["intermediates"])
    flat = collect_moe_metadata(state["intermediates"])
    (key,) = [k for k in flat if k.endswith("held_rows_share")]
    assert flat[key] == pytest.approx(int(received.sum()) / (40 * K))
    if share is not None:
        assert flat[key] == share


def test_the_layer_on_the_kernel_tier_matches_the_jnp_tier(monkeypatch):
    """The whole layer with the device gate answering "TPU" and every kernel
    in interpret mode (the two row kernels and JAX's ``gmm``) against the same
    layer as a CPU runs it."""
    import gigapath_tpu.ops.flash_attention as fa

    layer = DroplessMoE(M, 128, E, K, experts_held=4, dtype=jnp.float32,
                        param_dtype=jnp.float32)
    x = jnp.asarray(np.random.default_rng(3).standard_normal((S, M)), jnp.float32)
    params = layer.init(jax.random.PRNGKey(2), x)["params"]
    want, want_received = layer.apply({"params": params}, x)
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    with pltpu.force_tpu_interpret_mode():
        got, received = layer.apply({"params": params}, x)
    assert received.tolist() == want_received.tolist()
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
