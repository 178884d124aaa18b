"""MiniCPM-SALA on the CPU: the block selection against a brute force (ties,
forced first and local blocks, rows with fewer eligible blocks than the top
k, the dense length), the compressed-key scores and the block-sparse core on
both tiers against plain numpy, the lightning scan on both tiers against the
recurrence a position at a time and its decay table, the tiny model through
the scoring entry against ``benchmarks/lib/reference_minicpm_sala.py`` on
both tiers, the counters on a hand-built selection, and the stage's
parameter count at the published widths. The Pallas kernels run in interpret
mode."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.pallas import tpu as pltpu

from benchmarks.drivers.closed_loop import row_gaps
from benchmarks.lib import reference_minicpm_sala as reference
from benchmarks.lib import tables, weights_lm
from gigapath_tpu import pipeline
from gigapath_tpu.models import minicpm_sala
from gigapath_tpu.ops import block_sparse as bs
from gigapath_tpu.ops import pallas_block_sparse, pallas_ssd, ssd
from gigapath_tpu.utils.registry import create_model_from_registry

CONFIG = tables.load("configs", "minicpm_sala_pp8")
TINY = CONFIG["tiny"]


# ---- the selection -------------------------------------------------------


def _brute_select(scores, topk, block, init_blocks, window):
    """``scores [L, nb]`` of one sequence and group -> ``[L, topk]``: forced
    blocks first in rising order, then the rest by falling score, the lower
    block first among equals, a block of score -inf never, -1 after."""
    L, nb = scores.shape
    out = np.full((L, topk), -1, np.int64)
    for t in range(L):
        first_local = max(0, t - window + 1) // block
        ranked = []
        for b in range(min(nb, t // block + 1)):
            forced = b < init_blocks or b >= first_local
            if forced or scores[t, b] > -np.inf:
                ranked.append((0 if forced else 1, 0.0 if forced else -scores[t, b], b))
        taken = [b for _, _, b in sorted(ranked)[:topk]]
        out[t, :len(taken)] = taken
    return out


@pytest.mark.parametrize("L,block,topk,window", [
    (200, 8, 6, 8),     # the tiny preset's sizes: every row past 48 chooses
    (96, 16, 8, 40),    # fewer eligible blocks than the top k on every row
    (130, 8, 4, 17)])   # a window that straddles a block
def test_selection_is_the_brute_force_with_ties_to_the_lower_block(L, block, topk, window):
    rng = np.random.default_rng(L)
    nb = -(-L // block)
    # four levels, so that most rows hold ties; some blocks not visible (-inf)
    scores = rng.integers(0, 4, (2, 2, L, nb)).astype(np.float32) / 4
    scores[rng.random(scores.shape) < 0.1] = -np.inf
    got = np.asarray(bs.select_blocks(jnp.asarray(scores), topk=topk, block=block,
                                      init_blocks=1, window=window))
    assert got.shape == (2, 2, L, topk) and got.dtype == np.int32
    for b in range(2):
        for g in range(2):
            np.testing.assert_array_equal(
                got[b, g], _brute_select(scores[b, g], topk, block, 1, window))
    # block 0 and the query's own block are always taken; no later block ever
    t = np.arange(L)
    assert (got[..., 0] == 0).all()
    assert ((got == (t // block)[:, None]).any(-1)).all()
    assert (got <= (t // block)[:, None]).all()


def _brute_block_scores(q, k, kernel, stride, block, scale):
    """``q [L, H, d]``, ``k [L, G, d]`` -> ``[G, L, nb]`` float64."""
    L, H, d = q.shape
    G = k.shape[1]
    M = (L - kernel) // stride + 1
    nb = -(-L // block)
    kc = np.stack([k[stride * j:stride * j + kernel].mean(0) for j in range(M)])   # [M, G, d]
    out = np.full((G, L, nb), -np.inf)
    for t in range(L):
        visible = [j for j in range(M) if stride * j + kernel - 1 <= t]
        if not visible:
            continue
        for g in range(G):
            P = np.zeros(len(visible))
            for h in range(g * (H // G), (g + 1) * (H // G)):
                s = np.array([q[t, h] @ kc[j, g] for j in visible]) * scale
                e = np.exp(s - s.max())
                P += e / e.sum()
            for b in range(nb):
                over = [p for p, j in zip(P, visible)
                        if stride * j < block * (b + 1) and stride * j + kernel > block * b]
                if over:
                    out[g, t, b] = max(over)
    return out


@pytest.mark.parametrize("tier", ["jnp", "kernel"])
def test_block_scores_are_the_plain_softmax_sum_and_max_pool(tier):
    """Float32 inputs: the jnp tier to 1e-5 (summation order); the kernel
    multiplies bfloat16 operands with float32 sums, so its unit scores carry
    ~2^-9 relative error before the softmax: 2e-3 of a score that sums 8
    probabilities. The same blocks are -inf on both."""
    L, H, G, d = (77, 4, 2, 16) if tier == "jnp" else (300, 16, 2, 128)
    kernel, stride, block = (8, 4, 8) if tier == "jnp" else (32, 16, 64)
    rng = np.random.default_rng(5)
    q = rng.standard_normal((1, L, H, d)).astype(np.float32)
    k = rng.standard_normal((1, L, G, d)).astype(np.float32)
    scale = d ** -0.5
    want = _brute_block_scores(q[0], k[0], kernel, stride, block, scale)
    kw = dict(kernel=kernel, stride=stride, block=block, scale=scale)
    if tier == "jnp":
        got = bs.compressed_scores(jnp.asarray(q), jnp.asarray(k), **kw, use_pallas=False)
        atol = 1e-5
    else:
        assert pallas_block_sparse.score_fits(q.shape, k.shape, kernel, stride, block)
        got = bs.compressed_scores(jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
                                   **kw, use_pallas=True, interpret=True)
        atol = 2e-3 * (H // G)
    got = np.asarray(got)[0]
    assert got.shape == want.shape and got.dtype == np.float32
    np.testing.assert_array_equal(np.isfinite(got), np.isfinite(want))
    fin = np.isfinite(want)
    np.testing.assert_allclose(got[fin], want[fin], atol=atol)


# ---- the core ------------------------------------------------------------


def _masked_dense(q, k, v, selected, block, scale):
    """Softmax over every key ``s <= t`` of the selected blocks, float64."""
    B, L, H, d = q.shape
    G = k.shape[2]
    out = np.zeros((B, L, H, v.shape[-1]))
    keys = np.arange(L)
    for b in range(B):
        for g in range(G):
            chosen = np.zeros((L, -(-L // block)), bool)
            for t in range(L):
                row = selected[b, g, t]
                chosen[t, row[row >= 0]] = True
            mask = chosen[:, keys // block] & (keys[None, :] <= keys[:, None])
            for h in range(g * (H // G), (g + 1) * (H // G)):
                s = np.where(mask, (q[b, :, h] @ k[b, :, g].T) * scale, -np.inf)
                p = np.exp(s - s.max(-1, keepdims=True))
                out[b, :, h] = (p / p.sum(-1, keepdims=True)) @ v[b, :, g]
    return out


@pytest.mark.parametrize("tier", ["jnp", "kernel"])
def test_the_core_attends_to_the_selected_blocks_and_to_no_other(tier):
    """The selection of the scores of random q and k, then the core against a
    masked softmax over every key. Float32 on the jnp tier (1e-5); the kernel
    reads bfloat16 q, k, v and rounds the weights to bfloat16 before ``p v``:
    1e-2 of the largest value. A row whose tile holds blocks it did not
    choose must not read them: the tiles here hold 2-3 times a row's blocks."""
    B, L, H, G, d, block = (2, 150, 4, 2, 16, 8) if tier == "jnp" else (1, 300, 16, 2, 128, 16)
    rng = np.random.default_rng(7)
    q, k, v = (rng.standard_normal((B, L, n, d)).astype(np.float32) for n in (H, G, G))
    if tier == "kernel":
        q, k, v = (np.asarray(jnp.asarray(a, jnp.bfloat16), np.float32) for a in (q, k, v))
    scale = d ** -0.5
    scores = bs.compressed_scores(jnp.asarray(q), jnp.asarray(k), kernel=2 * block // 2,
                                  stride=block // 2, block=block, scale=scale, use_pallas=False)
    selected = bs.select_blocks(scores, topk=5, block=block, init_blocks=1, window=block)
    want = _masked_dense(q, k, v, np.asarray(selected), block, scale)
    if tier == "jnp":
        out, fetched = bs.block_sparse_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                                                 selected, block=block, scale=scale,
                                                 use_pallas=False)
        np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)
    else:
        assert pallas_block_sparse.fits(q.shape, k.shape, block, bs.TILE_Q,
                                        min(bs.TILE_Q * 5, -(-L // block)))
        out, fetched = bs.block_sparse_attention(
            *(jnp.asarray(a, jnp.bfloat16) for a in (q, k, v)), selected, block=block,
            scale=scale, use_pallas=True, interpret=True)
        assert out.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(out, np.float32), want,
                                   atol=1e-2 * np.abs(want).max())
    _, named = bs.selection_counts(selected, block)
    assert (np.asarray(fetched) > 1.5 * np.asarray(named)).all()
    # the blocks visited: the jnp tier gathers the most a list can name for
    # every tile, the kernel runs whole steps of BLOCKS_PER_STEP over its list
    lists, _ = bs.tile_lists(selected, -(-L // block))
    tiles = np.asarray(lists)[..., 0]
    if tier == "jnp":
        visited = np.full_like(tiles, min(bs.TILE_Q * 5, -(-L // block)))
    else:
        step = pallas_block_sparse.BLOCKS_PER_STEP
        visited = -(-tiles // step) * step
    np.testing.assert_array_equal(np.asarray(fetched), visited.sum(axis=(1, 2)) * bs.TILE_Q)


def test_the_counters_on_a_hand_built_selection():
    """One sequence, one group, 16 positions in blocks of 4, the top 2, tiles
    of 4 positions. Rows 0-3 name block 0 alone; rows 4-7 blocks {0, 1};
    rows 8-11 {0, 2} and {1, 2} by turns; rows 12-15 {2, 3}."""
    sel = np.full((1, 1, 16, 2), -1, np.int32)
    sel[0, 0, 0:4, 0] = 0
    sel[0, 0, 4:8] = [0, 1]
    sel[0, 0, 8:12] = [[0, 2], [1, 2], [0, 2], [1, 2]]
    sel[0, 0, 12:16] = [2, 3]
    pairs, named = bs.selection_counts(jnp.asarray(sel), 4)
    # keys s <= t of each named block: a whole block behind, t % 4 + 1 of its own
    want_pairs = (1 + 2 + 3 + 4) + 4 * 4 + (1 + 2 + 3 + 4) + 4 * 4 + (1 + 2 + 3 + 4) \
        + 4 * 4 + (1 + 2 + 3 + 4)
    assert int(pairs[0]) == want_pairs == 88
    assert int(named[0]) == 4 + 8 + 8 + 8
    lists, masks = bs.tile_lists(jnp.asarray(sel), 4, tile=4)
    lists, masks = np.asarray(lists)[0, 0], np.asarray(masks)[0, 0]
    assert [list(row[1:1 + row[0]]) for row in lists] == [[0], [0, 1], [0, 1, 2], [2, 3]]
    # bit p: the tile's position p named the block
    assert list(masks[2, 1:4]) == [0b0101, 0b1010, 0b1111]
    assert list(masks[1, 1:3]) == [0b1111, 0b1111]
    q = jnp.ones((1, 16, 2, 8), jnp.float32)
    kv = jnp.ones((1, 16, 1, 8), jnp.float32)
    # the core's tiles of 8: rows 0-7 name blocks {0, 1}, rows 8-15 all four;
    # the jnp tier gathers all four for each tile
    _, fetched = bs.block_sparse_attention(q, kv, kv, jnp.asarray(sel), block=4, scale=1.0,
                                           use_pallas=False)
    assert bs.TILE_Q == 8 and int(fetched[0]) == 8 * (4 + 4)


@pytest.mark.parametrize("L", [40, 64])
def test_up_to_dense_len_the_layer_is_causal_attention_and_counts_every_pair(L):
    rng = np.random.default_rng(L)
    q, k, v = (rng.standard_normal((1, L, n, 16)).astype(np.float32) for n in (4, 2, 2))
    spec = bs.SparseSpec(kernel_size=8, kernel_stride=4, block_size=8, topk=6, init_blocks=1,
                         window_size=8, dense_len=64)
    out, counters = bs.infllm_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), spec,
                                        scale=0.25)
    every = np.broadcast_to(np.arange(-(-L // 8)), (1, 2, L, -(-L // 8)))
    want = _masked_dense(q, k, v, every, 8, 0.25)
    np.testing.assert_allclose(np.asarray(out), want, atol=1e-5)
    assert int(counters["selected_pairs"][0]) == 2 * L * (L + 1) // 2
    assert int(counters["kv_blocks_selected"][0]) == int(counters["kv_blocks_fetched"][0]) \
        == 2 * sum(t // 8 + 1 for t in range(L))


# ---- the lightning scan --------------------------------------------------


def _recurrence(x, B, C, log_decay):
    """``S_t = lambda S_{t-1} + x_t B_t^T``, ``y_t = S_t C_t``, a position at a
    time, float64: ``x [b, L, H, P]``, ``B``, ``C`` ``[b, L, H, N]``."""
    b, L, H, P = x.shape
    S = np.zeros((b, H, P, B.shape[-1]))
    ys = []
    for t in range(L):
        S = np.exp(log_decay)[None, :, None, None] * S + x[:, t, :, :, None] * B[:, t, :, None, :]
        ys.append(np.einsum("bhpn,bhn->bhp", S, C[:, t]))
    return np.stack(ys, 1).reshape(b, L, H * P)


@pytest.mark.parametrize("tier,L", [("jnp", 77), ("jnp", 40), ("kernel", 300), ("kernel", 384)])
def test_the_lightning_scan_is_the_recurrence(tier, L):
    """The decays of layer 1's 32 heads from its table, spread over 16 heads
    (0.44 to 0.996 a position: the slowest remembers ~270 positions, so the
    state a chunk hands on matters). Float32: 2e-4 relative on the jnp tier;
    the kernel rounds its products' operands to its input type, float32 here,
    to the same. Run a chunk at a time, the state dropped, it misses."""
    H, P, chunk = (4, 8, 16) if tier == "jnp" else (16, 128, 128)
    rng = np.random.default_rng(L)
    x = rng.standard_normal((2, L, H, P)).astype(np.float32)
    B, C = (rng.standard_normal((2, L, H, P)).astype(np.float32) / np.sqrt(P) for _ in "BC")
    A = np.asarray(minicpm_sala.lightning_log_decay(1, 32, 32))[::32 // H]
    want = _recurrence(x, B, C, A)
    top = np.abs(want).max()

    def scan(x, B, C):
        if tier == "jnp":
            return ssd.linear_scan(jnp.asarray(x), jnp.asarray(B), jnp.asarray(C), jnp.asarray(A),
                                   chunk=chunk)
        assert pallas_ssd.fits(H, P, P, chunk, per_head=True)
        n = x.shape[1]
        return pallas_ssd.linear_scan_fwd(*(jnp.asarray(a).reshape(2, n, H * P) for a in (x, B, C)),
                                          jnp.asarray(A), chunk=chunk, interpret=True)

    got = np.asarray(scan(x, B, C))
    np.testing.assert_allclose(got, want, atol=2e-4 * top)
    dropped = np.concatenate([np.asarray(scan(x[:, s:s + chunk], B[:, s:s + chunk],
                                              C[:, s:s + chunk])) for s in range(0, L, chunk)], 1)
    assert np.abs(dropped - want).max() > 0.05 * top


def test_the_decay_table_of_layers_1_to_3():
    """MiniMax-01's schedule, ``lambda = exp(-2^(-8 (h + 1) / 32) (1 - l / 31
    + 1e-5))``: head 0 forgets fastest and the table rises with the layer;
    the program's and the reference's tables are one function."""
    for layer, (first, last) in {1: (0.44318006, 0.99622686), 2: (0.45536616, 0.99635240),
                                 3: (0.46788733, 0.99647795)}.items():
        lam = np.exp(np.asarray(minicpm_sala.lightning_log_decay(layer, 32, 32), np.float64))
        assert lam[0] == pytest.approx(first, rel=1e-6) and lam[-1] == pytest.approx(last, rel=1e-6)
        assert (np.diff(lam) > 0).all()
        want = [math.exp(-2 ** (-8 * (h + 1) / 32) * (1 - layer / 31 + 1e-5)) for h in range(32)]
        np.testing.assert_allclose(lam, want, rtol=1e-6)
        np.testing.assert_allclose(np.exp(reference.log_decay(layer, 32, 32)), want, rtol=1e-12)


def test_the_scan_group_axis_leaves_one_group_as_it_was():
    """A group a head, each head's ``B`` and ``C`` the one group's, is the
    one-group scan."""
    rng = np.random.default_rng(2)
    b, L, H, P, N = 2, 70, 4, 8, 16
    x = jnp.asarray(rng.standard_normal((b, L, H, P)), jnp.float32)
    dt = jnp.asarray(rng.uniform(0.01, 0.5, (b, L, H)), jnp.float32)
    A = -jnp.asarray(rng.uniform(0.2, 2.0, H), jnp.float32)
    B, C = (jnp.asarray(rng.standard_normal((b, L, N)), jnp.float32) for _ in "BC")
    D = jnp.asarray(rng.standard_normal(H), jnp.float32)
    one = ssd.ssd_scan_jnp(x, dt, A, B, C, D, chunk=16)
    per_head = ssd.ssd_scan_jnp(x, dt, A, *(jnp.broadcast_to(a[:, :, None], (b, L, H, N))
                                            for a in (B, C)), D, chunk=16)
    np.testing.assert_allclose(np.asarray(per_head), np.asarray(one), rtol=1e-5, atol=1e-5)


# ---- the model -----------------------------------------------------------


def _weights(model, seed, dtype=None):
    ids = jax.ShapeDtypeStruct((1, 4), jnp.int32)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids, ids)["params"]
    if dtype is not None:
        shapes = jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, dtype), shapes)
    return weights_lm.make_weights(shapes, seed)


def _sizes(model, **sparse):
    """The reference's sizes of a model built from the tiny preset."""
    c = model.cfg
    return {**TINY, "head_dim": c.head_dim, "num_attention_heads": c.num_attention_heads,
            "lightning_nh": c.lightning_nh, "lightning_head_dim": c.lightning_head_dim,
            "sparse_config": {**TINY["sparse_config"], **sparse}}


@pytest.mark.parametrize("seed", [11, 3000000019])
def test_the_tiny_model_through_the_entry_is_the_reference(seed):
    """Float32 parameters and activations: the program's jnp tier (its
    selection made from the same scores) and the reference agree to
    summation order, 1e-4 of a row, in the logits and in the sparse layer's
    core output at the same rows; every row's selection counted."""
    model = create_model_from_registry(TINY["arch"], dtype=jnp.float32, param_dtype=jnp.float32)
    params = _weights(model, seed, jnp.float32)
    L = 259
    ids = np.random.default_rng(seed).integers(0, TINY["vocab_size"], (2, L)).astype(np.int32)
    positions = np.array([[3, 100, 200, L - 1], [0, 49, 130, L - 1]], np.int32)
    out = pipeline.run_inference_with_lm(ids, positions, lm=(model, params))
    assert out["logits"].shape == (2, 4, TINY["vocab_size"]) and out["expert_tokens"].shape == (0,)
    assert out["core_rows"].shape == (1, 2, 4, 4 * 16) and out["core_rows"].dtype == np.float32
    for b in range(2):
        want, want_core = reference.forward(params, ids[b], positions[b], TINY)
        assert row_gaps(out["logits"][b], want).max() < 1e-4
        assert row_gaps(out["core_rows"][:, b], want_core).max() < 1e-4
        np.testing.assert_array_equal(reference.lm_forward(params, ids[b], positions[b], TINY), want)
    from benchmarks.lib import flops_minicpm_sala as flops

    assert out["selected_pairs"].shape == (1, 2)
    assert (out["selected_pairs"] == 2 * flops.selected_pairs(TINY, L)).all()
    assert (out["kv_blocks_fetched"] >= out["kv_blocks_selected"]).all()


def test_the_kernels_in_the_model_are_the_reference(monkeypatch):
    """The tiny stack at widths the kernels take (heads of 128, 16 query heads
    over 2 KV groups, 16 lightning heads, chunks of 128), the device gate
    answering "TPU" and every kernel in interpret mode: ``block_score``,
    ``block_sparse_attn`` and the scan's. bfloat16 as the cell runs: within
    the gaps the cell's tiny limits allow."""
    import gigapath_tpu.ops.flash_attention as fa

    widths = dict(head_dim=128, num_attention_heads=16, lightning_nh=16, lightning_nkv=16,
                  lightning_head_dim=128, lightning_chunk=128, sparse_block_size=16,
                  sparse_window_size=16, sparse_kernel_size=16, sparse_kernel_stride=8)
    model = create_model_from_registry(TINY["arch"], **widths)
    params = _weights(model, 5)
    L = 300
    ids = np.random.default_rng(5).integers(0, TINY["vocab_size"], (1, L)).astype(np.int32)
    positions = np.array([[20, 150, 240, L - 1]], np.int32)
    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    forward = pipeline.lm_forward_fn.__wrapped__(model)
    with pltpu.force_tpu_interpret_mode():
        text = forward.lower(params, ids, positions).as_text(debug_info=True)
        logits, _, counters = forward(params, ids, positions)
    for kernel in ("block_score", "block_sparse_attn", "ssd_scan_fwd"):
        assert kernel in text, kernel
    sizes = _sizes(model, block_size=16, window_size=16, kernel_size=16, kernel_stride=8)
    want, want_core = reference.forward(params, ids[0], positions[0], sizes)
    gaps = row_gaps(np.asarray(logits[0]), want)
    limits = tables.load("workloads", "sala_prefill_b1_64k")["correct"]["tiny_limits"]
    assert gaps.max() < limits["embed_gap_max"] and gaps.mean() < limits["embed_gap_mean"], gaps
    core_gaps = row_gaps(np.asarray(counters["core_rows"][:, 0]), want_core)
    assert core_gaps.mean() < limits["core_gap_mean"], core_gaps
    assert int(counters["kv_blocks_fetched"][0, 0]) > int(counters["kv_blocks_selected"][0, 0])


def test_the_stage_holds_its_published_parameter_count():
    """253,763,840 in the sparse layer, 285,225,216 in a lightning layer,
    300,843,008 in each of the embedding and the head, 4,096 in the final
    norm: 1,711,129,600 in the stage, 3.42 GB of bfloat16."""
    from benchmarks.systems.lm import System

    shapes = System(CONFIG, tiny=False).param_shapes()
    count = lambda tree: sum(x.size for x in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes["layers_0"]) == 253_763_840
    assert all(count(shapes[f"layers_{i}"]) == 285_225_216 for i in (1, 2, 3))
    sparse, lightning = shapes["layers_0"]["self_attn"], shapes["layers_1"]["self_attn"]
    assert sparse["k_proj"]["kernel"].shape == sparse["v_proj"]["kernel"].shape == (4096, 256)
    assert sparse["gate_proj"]["kernel"].shape == (4096, 4096) and "o_norm" not in sparse
    assert lightning["o_norm"]["weight"].shape == (4096,)
    assert count(shapes["layers_0"]["mlp"]) == 3 * 4096 * 16384
    assert count(shapes["embed_tokens"]) == count(shapes["lm_head"]) == 73448 * 4096
    assert count(shapes) == 1_711_129_600
    assert all(x.dtype == jnp.bfloat16 for x in jax.tree.leaves(shapes))
    whole = 8 * 253_763_840 + 24 * 285_225_216 + 2 * 300_843_008 + 4096
    assert whole == 9_477_206_016
