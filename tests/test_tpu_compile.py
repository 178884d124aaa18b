"""Compile the main path's Pallas kernels for a DESCRIBED TPU v5e.

No chip is attached here: the TPU compiler that ships with the installation
compiles for a topology that is described (``v5e:2x2``), and refuses what
the chip's compiler would refuse — a slice off the tiling, a kernel over its
scoped-VMEM budget (the failure that once reached the driver from the
m=1281 / 1408-block backward), a program that does not fit the device. A
compile that passes is not a chip run: it says nothing about results or time.

Kernels only, at flagship widths (H=16, Dh=48, the flagship schedule) and the
shapes ``chip_smoke.py`` executes, and one ViT-G/14 block around its attention
kernel; the 100-second whole-model compiles stay out of the suite.

Rules this file keeps (the on-chip-measurement guide, section 2): the
topology is described inside a module-scoped, non-autouse fixture that skips
when it cannot be — never at import, in a ``skipif`` or in a ``parametrize``
argument; every compile runs in the test's own process (the worker that
describes the topology holds the TPU library until it exits); all such tests
live in this one file; the persistent compile cache is off around them (a
cached entry for a described chip cannot be read back without one).
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

H, DH = 16, 48
N_BENCH = 10241          # bench N + the cls token
N_BUCKET = 16384 + 1     # the larger ragged PANDA bucket + cls
FOLD_CHUNK = 2048


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def schedule():
    from gigapath_tpu.models.longnet_config import flagship_geometry

    g = flagship_geometry()
    assert (g["heads"], g["head_dim"]) == (H, DH)
    return list(g["segment_lengths"]), list(g["dilated_ratios"])


def _compiled(fn, one_chip, *avals):
    """``fn`` compiled for the described chip (``avals``: a pytree of
    shapes). A kernel over its scoped-VMEM limit fails here."""
    args = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), avals
    )
    return jax.jit(fn).lower(*args).compile()


def _compile(fn, one_chip, *avals):
    """Compile ``fn`` for the described chip; returns the compiled text's
    kernel count (a dispatch that fell to the jnp tier compiles to zero)."""
    from gigapath_tpu.obs.ledger import custom_calls_of

    n = custom_calls_of(_compiled(fn, one_chip, *avals))
    assert n, "compiled program holds no tpu_custom_call"
    return n


def _kernel_calls(compiled) -> list:
    """The compiled text's ``tpu_custom_call`` instructions, one a line."""
    return [line.strip() for line in compiled.as_text().splitlines()
            if 'custom_call_target="tpu_custom_call"' in line]


def _picked(table: dict, operation: str) -> bool:
    """Whether the benchmark's own rule (``TraceReduction.kernel_seconds``)
    counts an operation of this HLO text under the kernel table."""
    from benchmarks.lib.trace import TraceReduction

    one = TraceReduction(window_s=1.0, busy_s=1.0, n_devices=1, op_total_s={operation: 1.0},
                         op_self_s={}, host_span_s={}, idle_gaps=[])
    return one.kernel_seconds(table) > 0


def _blhd(L, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct((1, L, H, DH), dtype)


def _sq_mean(o):
    return (o.astype(jnp.float32) ** 2).mean()


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("path", ["fused", "bhld"])
def test_dilated_attention_at_bench_length(topo, one_chip, schedule, path, grad):
    from gigapath_tpu.ops import dilated_attention as da

    segs, ratios = schedule

    def fwd(q, k, v):
        if path == "fused":
            return da.dilated_attention_fused(q, k, v, segs, ratios)
        return da.dilated_attention_bhld(q, k, v, segs, ratios, use_pallas=True)

    fn = fwd
    if grad:
        fn = jax.grad(lambda q, k, v: _sq_mean(fwd(q, k, v)), argnums=(0, 1, 2))
    x = _blhd(N_BENCH)
    calls = _kernel_calls(_compiled(fn, one_chip, x, x, x))
    assert calls, "compiled program holds no tpu_custom_call"
    if path != "fused":
        return
    # the fused op merges its branches in ONE epilogue call and unpacks no
    # branch in the forward; the benchmark's two kernel tables (by what a
    # call returns, by the name the program gives it) pick the five
    # attention kernels and nothing else, the epilogue least of all
    from benchmarks.lib import tables

    named = lambda name: [c for c in calls if re.match(rf"(ROOT )?%{name}[.\d]* = ", c)]
    assert len(named("dilated_epilogue_fwd")) == 1
    assert len(named("dilated_epilogue_bwd")) == (5 if grad else 0)
    assert len(named("dilated_unpack")) == (15 if grad else 0)  # dq, dk, dv a branch
    by_result = [c for c in calls if _picked(tables.kernel_table("dilated_attn"), c)]
    by_name = [c for c in calls if _picked(tables.kernel_table("dilated_fwd_by_name"), c)]
    # every branch of the non-causal schedule takes the overlapped body
    assert by_result == by_name == named("dilated_fwd_overlap") and len(by_name) == 5


def test_fused_grad_ragged_bucket_traced_valid_len(topo, one_chip, schedule):
    """The fine-tune train path: the 16,384 bucket, ``valid_len`` a traced
    [B] count (it rides the kernels' SMEM tables)."""
    from gigapath_tpu.ops import dilated_attention as da

    segs, ratios = schedule

    def loss(q, k, v, valid_len):
        return _sq_mean(
            da.dilated_attention_fused(q, k, v, segs, ratios, valid_len=valid_len)
        )

    x = _blhd(N_BUCKET)
    _compile(
        jax.grad(loss, argnums=(0, 1, 2)), one_chip,
        x, x, x, jax.ShapeDtypeStruct((1,), jnp.int32),
    )


@pytest.mark.parametrize("grad", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("head_dim,tokens", [(DH, N_BENCH), (64, N_BENCH), (96, N_BENCH),
                                             (DH, 4096 + 1), (96, 4096 + 1)])
def test_merge_epilogue_alone_at_the_padded_length(topo, one_chip, schedule, head_dim, tokens, grad):
    """The merge epilogue by itself on packed results of the schedule's five
    branches at L = 10,368 (the benchmark's slides: r = 2 through element
    windows that straddle its segment) and L = 4,224 (a serve bucket: every
    branch one segment or on the block grid, so fewer windows and, were the
    token block not held to 256, a step too large), heads of 48 and the
    wider encoders' 64 and 96 (E = 768 / 1,024 / 1,536): the plan promises
    ONE forward call, a backward call a branch, and the kernel has to fit
    the scoped VMEM a kernel has by default (one over it fails to compile;
    one that asked for more hung the chip inside the whole model, PR 33)."""
    from gigapath_tpu.ops import pallas_dilated as pd

    L = -(-tokens // 128) * 128
    E = H * head_dim
    plan = pd.plan_stream_fusion(L, E, H, *schedule)
    assert plan.BT == 256
    assert plan.straddles == (None, (80,) if L > 5792 else None, None, None, None)
    assert pd._epilogue_vmem(plan.BT, E, H, head_dim, plan.branches, plan.straddles,
                             2) <= pd._EPILOGUE_VMEM_BUDGET
    outs = tuple(jax.ShapeDtypeStruct((2, S, r, hb, Mp, head_dim), jnp.bfloat16)
                 for r, hb, S, g, Mp in plan.branches)
    lses = tuple(jax.ShapeDtypeStruct((2, S, r, Mp, 128), jnp.float32)
                 for r, hb, S, g, Mp in plan.branches)

    def fn(outs, lses):
        return pd._fusion_epilogue(outs, lses, plan)

    if grad:
        fn = jax.grad(lambda outs, lses: _sq_mean(pd._fusion_epilogue(outs, lses, plan)))
    calls = _kernel_calls(_compiled(fn, one_chip, outs, lses))
    forward = [c for c in calls if re.match(r"(ROOT )?%dilated_epilogue_fwd[.\d]* = ", c)]
    backward = [c for c in calls if re.match(r"(ROOT )?%dilated_epilogue_bwd[.\d]* = ", c)]
    assert len(forward) == 1 and len(backward) == (5 if grad else 0)
    # one bf16 result in the forward; statistics first under differentiation
    result = re.match(r"(ROOT )?%[\w.]+ = (\(?\w+)\[", forward[0]).group(2)
    assert result == ("(f32" if grad else "bf16")


@pytest.mark.parametrize("branch", range(5))
@pytest.mark.parametrize("head_dim,tokens", [(DH, N_BENCH), (DH, 4096 + 1), (64, N_BENCH),
                                             (96, N_BENCH)])
def test_forward_body_of_each_branch_alone(topo, one_chip, schedule, head_dim, tokens, branch):
    """The forward body the planner names, on packed arrays of each of the
    schedule's five branches at L = 10,368 (the benchmark's slides), at
    L = 4,224 (a serve bucket: smaller blocks, r8 and r16 one key block) and
    at the wider encoders' heads of 64 and 96: the overlapped body, two
    heads a step for r1-r8 and one for r16, has to fit the scoped VMEM a
    kernel has by default (two heads' blocks, their stats and four chains'
    logit tiles at 1,024 x 512; three heads a step, or the lagged form at a
    key block of 1,024, do not), and returns the serial body's
    ``(bf16, f32)`` pair under a name the benchmark's tables find."""
    from benchmarks.lib import tables
    from gigapath_tpu.ops import pallas_dilated as pd

    L = -(-tokens // 128) * 128
    sl, r = schedule[0][branch], schedule[1][branch]
    g, S, _, _, Mp, block = pd._branch_geometry(L, H * head_dim, sl, r)
    hb = H // r
    assert pd.plan_fwd_body(False, hb, block) == pd.FwdPlan(
        "overlap", 2 if hb > 1 else 1, block // 2)
    x6 = jax.ShapeDtypeStruct((2, S, r, hb, Mp, head_dim), jnp.bfloat16)
    calls = _kernel_calls(_compiled(
        lambda q6, k6, v6, kvlen: pd._packed_forward(
            q6, k6, v6, kvlen, False, hb, head_dim, block, False),
        one_chip, x6, x6, x6, jax.ShapeDtypeStruct((2, S, r), jnp.int32)))
    assert len(calls) == 1 and re.match(r"(ROOT )?%dilated_fwd_overlap[.\d]* = \(bf16\[", calls[0])
    for table in ("dilated_attn", "dilated_fwd_by_name"):
        assert _picked(tables.kernel_table(table), calls[0]), table


@pytest.mark.parametrize("head_dim,branch", [
    (DH, 0), (DH, 1), (DH, 2), (DH, 3), (DH, 4), (64, 1), (64, 3), (96, 1), (96, 4)])
def test_copy_kernels_at_the_padded_bench_length(topo, one_chip, schedule, head_dim, branch):
    """The pack / unpack copy kernels on the dense [B, L, E] array at the
    length the slide encoder hands them (10,241 padded to 10,368): blocked
    windows for four branches, hand-copied ones for r = 2 (g = 5,792), and
    the wider encoders' heads of 64 and 96 (E = 1,024 / 1,536: other row
    blocks under the same VMEM budget; 64 at r = 8 was over it at 4 MiB)."""
    from gigapath_tpu.ops import pallas_dilated as pd

    L = -(-N_BENCH // 128) * 128
    E = H * head_dim
    sl, r = schedule[0][branch], schedule[1][branch]
    g, S, _, _, Mp, _ = pd._branch_geometry(L, E, sl, r)
    assert pd._copy_plan(L, g, S, r, Mp, E, 2)[0] == ("element" if r == 2 else "grid")
    _compile(lambda x: pd._pack_phases(x, g, S, r, Mp, H, False), one_chip,
             jax.ShapeDtypeStruct((2, L, E), jnp.bfloat16))
    _compile(lambda p6: pd._unpack_phases(p6, L, E, g, S, r, False), one_chip,
             jax.ShapeDtypeStruct((2, S, r, H // r, Mp, head_dim), jnp.bfloat16))


@pytest.mark.parametrize("branch", range(5))
def test_pair_partial_flagship_pairs(topo, one_chip, schedule, branch):
    """The streaming fold kernel at chunk 2,048, forward and backward, for
    each flagship (segment, ratio) pair."""
    from gigapath_tpu.ops.pallas_streaming import pallas_pair_partial

    segs, ratios = schedule
    sl, r = int(segs[branch]), int(ratios[branch])

    def loss(q, k, v, q0, k0, valid_len):
        out, lse = pallas_pair_partial(
            q, k, v, q0, k0, segment_len=sl, ratio=r, valid_len=valid_len
        )
        return _sq_mean(out) + lse.mean()

    x = _blhd(FOLD_CHUNK)
    scalar = jax.ShapeDtypeStruct((), jnp.int32)
    _compile(
        jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip,
        x, x, x, scalar, scalar, scalar,
    )


def test_r03_branch_backward(topo, one_chip):
    """The shape that died on the driver in round 3: the flagship r=8 branch
    at N=10,241 has sparse length m=1,281 and picks one 1,408 forward block;
    squared in the backward it overflowed scoped VMEM (20.12 MB > 16 MB).
    ``bwd_blocks`` now splits the k side — this compiles the backward the
    chip's compiler once refused."""
    from gigapath_tpu.ops import dilated_attention as da

    sl, r = 185363, 8
    *_rest, m, block = da._bhld_geom(N_BENCH, sl, r)
    assert (m, block) == (1281, 1408)

    def loss(q, k, v):
        out, _ = da._branch_bhld(
            q, k, v, sl, r, is_causal=False, real_len=N_BENCH,
            interpret=False, use_pallas=True,
        )
        return _sq_mean(out)

    x = jax.ShapeDtypeStruct((1, H, N_BENCH, DH), jnp.bfloat16)  # head-major
    _compile(jax.value_and_grad(loss, argnums=(0, 1, 2)), one_chip, x, x, x)


def test_flash_and_flat_segment_standalone(topo, one_chip):
    from gigapath_tpu.ops.pallas_flash import flat_segment_flash, pallas_flash_attention

    def flash_loss(q, k, v):
        out, lse = pallas_flash_attention(q, k, v)
        return _sq_mean(out) + lse.mean()

    x = _blhd(2048)
    _compile(jax.value_and_grad(flash_loss, argnums=(0, 1, 2)), one_chip, x, x, x)

    def flat_loss(q, k, v):
        out, lse = flat_segment_flash(q, k, v, segment_len=1024, real_len=N_BENCH)
        return _sq_mean(out) + lse.mean()

    xh = jax.ShapeDtypeStruct((1, H, N_BENCH, DH), jnp.bfloat16)
    _compile(jax.value_and_grad(flat_loss, argnums=(0, 1, 2)), one_chip, xh, xh, xh)


# heads, KV heads, keys' width, values' width: the three cells' cores
_CAUSAL_CORES = {"axk1": (64, 64, 192, 128), "granite": (32, 8, 128, 128),
                 "dsv32": (128, 128, 192, 128)}


@pytest.mark.parametrize("tokens", [16384, 32768])
@pytest.mark.parametrize("core", _CAUSAL_CORES)
def test_overlapped_forward_body_alone_at_the_published_widths(topo, one_chip, core, tokens):
    """The body ``pallas_flash.plan_fwd_body`` names for a causal call and for
    the core over selected keys, alone, at the three cells' heads and widths
    and at twice their 16,384 tokens: blocks of 1,024 x 1,024, four chains of
    256 rows, the q block scaled once a row into a scratch of its own, the
    steps named by three prefetched tables, have to fit the 16 MiB of scoped
    VMEM a kernel has by default (with keys of 192 a ``[1024, 192]`` block
    takes 256 lanes; DeepSeek-V3.2's call adds two int8 tiles of the
    selection), under a name the benchmark's tables find. The selection is
    ``[1, L, L]``: 1.07 GB at 32,768."""
    from benchmarks.lib import tables
    from gigapath_tpu.ops import pallas_flash as pf
    from gigapath_tpu.ops.pallas_sparse import sparse_attn_fwd

    heads, kv_heads, d, dv = _CAUSAL_CORES[core]
    assert pf.plan_fwd_body("selection" if core == "dsv32" else "causal", pf.DEFAULT_BLOCK_Q,
                            (tokens // 1024) ** 2) == pf.FwdPlan("overlap", 256)
    bf16 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.bfloat16)
    if core == "dsv32":
        q, v = bf16((1, tokens, heads, d)), bf16((1, tokens, heads, dv))
        calls = _kernel_calls(_compiled(
            lambda q, k, v, mask: sparse_attn_fwd(q, k, v, mask, scale=0.1352),
            one_chip, q, q, v, jax.ShapeDtypeStruct((1, tokens, tokens), jnp.int8)))
        name, result, table = "sparse_attn_overlap", r"bf16\[", "sparse_attn_by_name"
    else:
        calls = _kernel_calls(_compiled(
            lambda q, k, v: pf._fwd_impl(q, k, v, None, True, 0.1, pf.DEFAULT_BLOCK_Q,
                                         pf.DEFAULT_BLOCK_K, False),
            one_chip, bf16((1, heads, 1, tokens, d)), bf16((1, kv_heads, 1, tokens, d)),
            bf16((1, kv_heads, 1, tokens, dv))))
        name, result, table = "flash_fwd_overlap", r"\(bf16\[", "flash_fwd_by_name"
    assert len(calls) == 1 and re.match(rf"(ROOT )?%{name}[.\d]* = {result}", calls[0]), calls
    assert _picked(tables.kernel_table(table), calls[0])


def test_overlapped_forward_step_tables_fit_at_the_planners_cap(topo, one_chip):
    """The three prefetched step tables at the longest call the planner sends
    to the overlapped body (a causal 262,144 tokens in blocks of 1,024:
    65,536 pairs of blocks, 32,896 of them visited): 395 KB of the 1 MiB of
    SMEM, beside the table of valid-key counts. Past it the planner keeps
    the serial body."""
    from gigapath_tpu.ops import pallas_flash as pf

    tokens = 256 * 1024
    assert pf.plan_fwd_body("causal", 1024, 256 * 256).body == "overlap"
    assert pf.plan_fwd_body("causal", 1024, 257 * 257).body == "serial"
    q = jax.ShapeDtypeStruct((1, 2, 1, tokens, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1, 1, tokens, 128), jnp.bfloat16)
    calls = _kernel_calls(_compiled(
        lambda q, k, v: pf._fwd_impl(q, k, v, None, True, 0.1, 1024, 1024, False),
        one_chip, q, kv, kv))
    assert len(calls) == 1 and "s32[32896]" in calls[0]


def test_quant_kernels_at_vit_g_widths(topo, one_chip):
    """``quant/`` Pallas tiers at the tile encoder's widths: d=1536 into the
    SwiGLU hidden 8192 over a batch-128 x 197-token activation, and int8-logit
    attention over heads of 64 (block-aligned length: 197 itself rides the
    reference tier by design)."""
    from gigapath_tpu.quant.qflash import q_flash_attention_pallas
    from gigapath_tpu.quant.qmatmul import q_matmul_pallas
    from gigapath_tpu.quant.qtensor import QTensor

    def matmul(x, w_q, scale):
        return q_matmul_pallas(x, QTensor(w_q, scale))

    _compile(
        matmul, one_chip,
        jax.ShapeDtypeStruct((128, 197, 1536), jnp.bfloat16),
        jax.ShapeDtypeStruct((1536, 8192), jnp.int8),
        jax.ShapeDtypeStruct((1, 8192), jnp.float32),
    )
    x = jax.ShapeDtypeStruct((8, 256, 24, 64), jnp.bfloat16)
    _compile(lambda q, k, v: q_flash_attention_pallas(q, k, v), one_chip, x, x, x)


def test_vit_block_at_full_width_holds_one_kernel(topo, one_chip, monkeypatch):
    """One ViT-G/14 block at the cell's batch (B=128, N=197, D=1536, 24 heads
    of 64) with the device gate answering "TPU": the attention core is the
    one custom call, reading the qkv GEMM's output and feeding ``proj``."""
    import gigapath_tpu.ops.flash_attention as fa
    from gigapath_tpu.models.tile_encoder import ViTBlock

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    block = ViTBlock(dim=1536, num_heads=24, mlp_hidden_dim=8192, dtype=jnp.bfloat16)
    x = jax.ShapeDtypeStruct((128, 197, 1536), jnp.bfloat16)
    params = jax.eval_shape(block.init, jax.random.PRNGKey(0), x)
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), (params, x)
    )
    from gigapath_tpu.obs.ledger import custom_calls_of

    compiled = jax.jit(block.apply).lower(*avals).compile()
    assert custom_calls_of(compiled) == 1
    # the name the trace's reduction finds it by (benchmarks/scopes/tile.json)
    assert re.search(
        r'op_name="[^"]*/attn/attn_core/jit\(packed_qkv_attention\)/kernel_fwd/vit_attn_fwd/',
        compiled.as_text())


def test_fused_local_branches_inside_shard_map_on_four_chips(topo, schedule):
    """The sequence-parallel recipe's LOCAL branches on a four-chip ``seq``
    mesh: per shard they are the single-device fused kernels, inside
    ``shard_map(check_vma=False)`` — a program no CPU platform ever built.
    16,384 tokens per chip, as ``chip_smoke.py --chips 4`` runs it."""
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from gigapath_tpu.ops.pallas_dilated import dilated_branch_attention

    segs, ratios = schedule
    mesh = Mesh(np.array(topo.devices[:4]), ("seq",))
    L, E = 4 * 16384, H * DH

    def local(q, k, v):
        outs = []
        for sl, r in zip(segs, ratios):
            if sl > q.shape[1]:
                continue  # gathered branches take the generic path
            out, _ = dilated_branch_attention(q, k, v, int(sl), int(r), H)
            outs.append(out)
        assert len(outs) == 2  # segments 1,024 and 5,792 are local
        return sum(outs)

    fn = jax.jit(shard_map(
        local, mesh=mesh, in_specs=(P(None, "seq"),) * 3,
        out_specs=P(None, "seq"), check_vma=False,
    ))
    x = jax.ShapeDtypeStruct(
        (1, L, E), jnp.bfloat16, sharding=NamedSharding(mesh, P(None, "seq"))
    )
    from gigapath_tpu.obs.ledger import custom_calls_of

    assert custom_calls_of(fn.lower(x, x, x).compile())


@pytest.mark.parametrize("piece", ["attention", "experts"])
def test_granite_layers_at_published_widths_hold_their_kernels(topo, one_chip, monkeypatch, piece):
    """granite-4.0-h-small's two kernel-bearing layers at the cell's 16,384
    tokens, the device gate answering "TPU": the causal core is one
    ``flash_fwd_overlap`` call over 32 query and 8 KV heads of 128 (no repeated K/V in
    memory); the dropless expert layer, 36 of 72 experts held, is one
    ``moe_dispatch``, two ``gmm`` and one ``moe_combine`` call, and no gather
    of the ``[163840, 4096]`` sorted buffer is left to XLA. Each stands under
    the scope the trace's reduction finds it by (benchmarks/scopes/lm.json,
    benchmarks/kernels/*_by_name.json)."""
    import gigapath_tpu.ops.flash_attention as fa
    from gigapath_tpu.models.granite_hybrid import CausalGQAttention
    from gigapath_tpu.obs.ledger import custom_calls_of
    from gigapath_tpu.ops.moe import DroplessMoE

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    if piece == "attention":
        layer, shape = CausalGQAttention(4096, 32, 8, 1 / 128), (1, 16384, 4096)
        kernels = {"flash_fwd_overlap": (1, r"attn_core/kernel_fwd/flash_fwd_overlap/")}
    else:
        layer, shape = DroplessMoE(4096, 768, 72, 10, experts_held=36), (16384, 4096)
        kernels = {"moe_dispatch": (1, r"dispatch/kernel_fwd/jit\(_dispatch_call\)/moe_dispatch/"),
                   "gmm": (2, r"experts/kernel_fwd/jit\(gmm\)/"),
                   "moe_combine": (1, r"combine/kernel_fwd/jit\(_combine_call\)/moe_combine/")}
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), (params, x))
    compiled = jax.jit(layer.apply).lower(*avals).compile()
    assert custom_calls_of(compiled) == sum(n for n, _ in kernels.values())
    text = compiled.as_text()
    for kernel, (calls, scope) in kernels.items():
        assert re.search(rf'op_name="[^"]*/{scope}', text), kernel
        # the instruction name the by-name kernel tables look for in the trace
        assert len(re.findall(rf"%{kernel}(?:\.\d+)? = [^\n]* custom-call\(", text)) == calls
    if piece == "attention":  # a KV head is read where it lies: no [.., 32, 128] copy of k or v
        assert not re.search(r"broadcast[^\n]*bf16\[1,16384,8,4,128\]", text)
    else:
        # rows move by the kernels' own copies: XLA gathers no sorted buffer and
        # builds no [S, k, M] array for the weighted sum
        # benchmarks/kernels/moe_gmm_by_name.json takes every custom call whose
        # text holds "%gmm", operands included: the two products and no other
        # (moe_combine reads the second one's result through a tiled view)
        assert len(re.findall(r"\n[^\n]*%gmm[^\n]* custom-call\(|\n[^\n]* custom-call\([^\n]*%gmm", text)) == 2
        assert not re.search(r"bf16\[163840,4096\][^\n]* gather\(", text)
        assert not re.search(r"(bf16|f32)\[16384,10,4096\]", text)
        # fast memory the two kernels ask for, of the 128 MiB a v5e core has:
        # two slots of 256 gathered rows and two output blocks (8 MB), and two
        # slots of 64 x 10 gathered rows, three blocks of 256 and three of 64
        # (17.5 MB), both inside the 48 MB the calls are allowed
        from gigapath_tpu.ops.moe import pallas_rows

        assert 4 * pallas_rows.DISPATCH_ROWS * 8192 == 8 << 20
        assert pallas_rows._combine_vmem(4096, 10, 2) == 18_350_080 < pallas_rows._VMEM_BUDGET


@pytest.mark.parametrize("piece", ["attention", "experts"])
def test_axk1_layers_at_published_widths_hold_their_kernels(topo, one_chip, monkeypatch, piece):
    """A.X-K1's two kernel-bearing pieces at the cell's 16,384 tokens, the
    device gate answering "TPU": latent attention is one ``flash_fwd_overlap`` call
    over 64 heads whose keys are 192 wide and whose values and output are 128
    (no value is padded to the keys' width, and no stride-2 gather is left of
    the rotary pairing); the dropless expert layer, 12 of 192 experts held
    behind the sigmoid group-limited gate, is one ``moe_dispatch``, two ``gmm``
    and one ``moe_combine`` call at rows of 7,168. Each stands under the scope
    the trace's reduction finds it by (benchmarks/scopes/axk1.json,
    benchmarks/kernels/flash_fwd_by_name.json, expert_gmm_by_name.json)."""
    import gigapath_tpu.ops.flash_attention as fa
    from gigapath_tpu.models.axk1 import MLAttention
    from gigapath_tpu.obs.ledger import custom_calls_of
    from gigapath_tpu.ops.moe import DroplessMoE, GroupLimitedSigmoidGate, pallas_rows
    from gigapath_tpu.utils.registry import create_model_from_registry

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    cfg = create_model_from_registry("axk1").cfg
    if piece == "attention":
        layer = MLAttention(cfg)
        shapes = (jax.ShapeDtypeStruct((1, 16384, 7168), jnp.bfloat16),
                  jax.ShapeDtypeStruct((16384, 32), jnp.float32),
                  jax.ShapeDtypeStruct((16384, 32), jnp.float32))
        kernels = {"flash_fwd_overlap": (
            1, r"attn_core/jit\(_causal_core\)/kernel_fwd/flash_fwd_overlap/")}
    else:
        assert pallas_rows.fits(16384, 7168, 8, jnp.bfloat16)
        layer = DroplessMoE(7168, 2048, 192, 8, experts_held=12,
                            gate=GroupLimitedSigmoidGate(8, 4, 2.5))
        shapes = (jax.ShapeDtypeStruct((16384, 7168), jnp.bfloat16),)
        kernels = {"moe_dispatch": (1, r"dispatch/kernel_fwd/jit\(_dispatch_call\)/moe_dispatch/"),
                   "gmm": (2, r"experts/kernel_fwd/jit\(gmm\)/"),
                   "moe_combine": (1, r"combine/kernel_fwd/jit\(_combine_call\)/moe_combine/")}
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), *shapes)
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), (params, *shapes))
    compiled = jax.jit(layer.apply).lower(*avals).compile()
    assert custom_calls_of(compiled) == sum(n for n, _ in kernels.values())
    text = compiled.as_text()
    for kernel, (calls, scope) in kernels.items():
        assert re.search(rf'op_name="[^"]*/{scope}', text), kernel
        # anchored at the instruction's own name, as kernels/expert_gmm_by_name.json is
        assert len(re.findall(rf"\n\s*(?:ROOT )?%{kernel}(?:\.\d+)? = [^\n]* custom-call\(", text)) == calls
    if piece == "attention":
        call = re.search(r"%flash_fwd_overlap(?:\.\d+)? = \((\S+) [^\n]* custom-call\([^\n]*"
                         r"operand_layout_constraints=\{((?:[^}]*\}){6})", text)  # 3 step tables, q, k, v
        assert call.group(1).startswith("bf16[1,64,1,16384,128]")   # out at the values' width
        assert call.group(2).count("bf16[1,64,1,16384,192]") == 2   # q and k at the keys'
        assert call.group(2).count("bf16[1,64,1,16384,128]") == 1   # v at its own
        assert " gather(" not in text
    else:
        assert not re.search(r"bf16\[131072,7168\][^\n]* gather\(", text)
        assert not re.search(r"(bf16|f32)\[16384,8,7168\]", text)
        # the combine's buffers at rows of 7,168 and top-8, of the budget fits() holds them to
        assert pallas_rows._combine_vmem(7168, 8, 2) <= pallas_rows._VMEM_BUDGET


def test_deepseek_v32_attention_at_published_widths_holds_its_kernels(topo, one_chip, monkeypatch):
    """DeepSeek-V3.2's attention at the cell's 16,384 tokens, the device gate
    answering "TPU": one ``index_score`` call over 64 index heads of 128 whose
    result is the ``[L, L]`` float32 scores (no ``[64, L, L]`` anywhere), one
    ``index_select`` call that turns them into the int8 selection, one
    ``sparse_attn_overlap`` call over 128 heads whose keys are 192 wide and whose values
    and output are 128, each under the scope the trace's reduction finds it by
    (benchmarks/scopes/dsv32.json, benchmarks/kernels/*_by_name.json); no
    ``flash_fwd``, no sort and no top-k is left in the program."""
    import gigapath_tpu.ops.flash_attention as fa
    from gigapath_tpu.models.deepseek_v32 import SparseMLAttention
    from gigapath_tpu.obs.ledger import custom_calls_of
    from gigapath_tpu.utils.registry import create_model_from_registry

    monkeypatch.setattr(fa, "_on_tpu", lambda: True)
    layer = SparseMLAttention(create_model_from_registry("deepseek_v32").cfg)
    shapes = (jax.ShapeDtypeStruct((1, 16384, 7168), jnp.bfloat16),
              jax.ShapeDtypeStruct((16384, 32), jnp.float32),
              jax.ShapeDtypeStruct((16384, 32), jnp.float32))
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), *shapes)
    avals = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), (params, *shapes))
    compiled = jax.jit(layer.apply).lower(*avals).compile()
    assert custom_calls_of(compiled) == 3
    text = compiled.as_text()
    for kernel, scope in (("index_score", r"indexer/score/kernel_fwd/index_score"),
                          ("index_select", r"select/kernel_fwd/index_select"),
                          ("sparse_attn_overlap", r"attn_core/kernel_fwd/sparse_attn_overlap")):
        assert re.search(rf'op_name="[^"]*/{scope}', text), kernel
        # anchored at the instruction's own name, as the kernel tables are
        assert len(re.findall(rf"\n\s*(?:ROOT )?%{kernel}(?:\.\d+)? = [^\n]* custom-call\(", text)) == 1
    assert re.search(r"%index_score(?:\.\d+)? = f32\[1,16384,16384\]", text)
    assert re.search(r"%index_select(?:\.\d+)? = s8\[1,16384,16384\]", text)
    assert re.search(r"%sparse_attn_overlap(?:\.\d+)? = bf16\[1,128,16384,128\]", text)
    assert "[1,64,16384,16384]" not in text and "[64,16384,16384]" not in text
    assert " sort(" not in text and " topk(" not in text and "%flash_fwd" not in text
    # the temporaries of one layer's attention beside 6.45 GB of weights on a 16 GB chip
    assert compiled.memory_analysis().temp_size_in_bytes < 6.5e9
