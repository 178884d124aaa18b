"""Pallas TPU kernel of the Mamba-2 chunked scan (:mod:`gigapath_tpu.ops.ssd`
has the mathematics and the dispatch): ``ssd_scan_fwd``, which also runs
decayed linear attention (``ssd.linear_scan``, a ``B`` / ``C`` group a head).

One grid step is one chunk of ``HEADS_PER_STEP`` heads; the chunk axis runs
in order and the heads' states stay in VMEM scratch from one chunk to the
next, so no chunk state and no entering state ever reaches HBM. ``x``, ``B``
and ``C`` are read by their BlockSpecs where the convolution leaves them, in
``xBC [b, L, H P + 2 N]`` (``x`` the first ``H P`` columns, then ``B``, then
``C``), and ``y`` is written as ``[b, L, H P]``, the gate norm's layout.

Per step, with ``Q`` the chunk and the state of the step's heads held
transposed, ``S^T [N, heads x P]`` float32, a 128-lane group at a time (heads
of 64 go two to a group):

- ``C B^T [Q, Q]`` once (one group: the heads share ``B`` and ``C``), or a
  head at a time from its own lanes of ``B`` and ``C`` (a group a head:
  :func:`linear_scan_fwd`, whose ``x``, ``B`` and ``C`` are three arrays);
- the masked, decayed product ``(C B^T o exp(acs_i - acs_j) o dt_j) x`` on
  the lower triangle, the exponent masked as the jnp tier has it, the blocks
  of 128 rows above the diagonal skipped; the group's heads are stacked along
  the contraction of one product, each against its own lanes of ``x``;
- the carried read ``exp(acs_i) C S^T`` before the update ``S^T <-
  exp(acs_end) S^T + (B^T o exp(acs_end - acs_j) dt_j) x``, whose weights
  scale ``B^T``'s columns (a row a head: no broadcast across lanes);
- ``+ D x`` in float32 and one rounding of ``y``.

Matrix operands in ``xBC``'s type with float32 accumulation, the state
float32: the jnp tier's precision (the update rounds the weighted ``B`` where
the jnp tier rounds the weighted ``x``). ``dt`` and ``acs`` (the running sum
of ``dt A`` within each chunk) come in with heads on the sublanes and
positions on the lanes; one transpose a step gives ``acs`` with positions on
the sublanes. Forward only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from gigapath_tpu.ops.common import round_up

HEADS_PER_STEP = 16
LANES = 128
# rows of a chunk's own product taken at a time: the blocks above the diagonal
# are skipped, and only those on it are masked
ROWS = 128


def fits(heads: int, head_dim: int, state_size: int, chunk: int, per_head: bool = False) -> bool:
    """Whether the kernel takes these widths: the step's heads fill whole
    128-lane groups (a head is a divisor or a multiple of 128 wide), ``B``
    and ``C`` are whole lane groups at a multiple of ``N`` into ``xBC``, and
    a chunk is whole lane groups. With a group a head (``per_head``), a head
    is one lane group of ``x`` and of ``B`` and ``C``."""
    if per_head:
        return heads % HEADS_PER_STEP == 0 and head_dim == state_size == LANES and chunk % LANES == 0
    return (heads % HEADS_PER_STEP == 0 and state_size % LANES == 0 and chunk % LANES == 0
            and (LANES % head_dim == 0 or head_dim % LANES == 0)
            and HEADS_PER_STEP * head_dim % LANES == 0
            and heads * head_dim % state_size == 0)


def _scan_kernel(x_ref, b_ref, c_ref, acs_ref, dt_ref, d_ref, y_ref, st_ref, *,
                 head_dim, chunk, per_head=False):
    Q, P, f32 = chunk, head_dim, jnp.float32
    hb = acs_ref.shape[0]
    width = max(P, LANES)           # a lane group: the lanes one product covers
    per_group = width // P          # heads in a lane group
    dtype = x_ref.dtype

    @pl.when(pl.program_id(2) == 0)
    def _start():
        st_ref[...] = jnp.zeros(st_ref.shape, f32)

    acs, dt = acs_ref[...], dt_ref[...]                             # [hb, Q]
    # the update's weights exp(acs_end - acs_j) dt_j, a row a head
    weights = jnp.exp(acs[:, Q - 1:Q] - acs) * dt
    # acs with positions on the sublanes: one transpose a step
    acs_col = jnp.concatenate([acs, jnp.zeros((LANES - hb, Q), f32)], axis=0).T[:, :hb]
    reads, ends = jnp.exp(acs_col), jnp.exp(acs_col[Q - 1:Q, :])    # [Q, hb], [1, hb]

    if per_head:  # each head's decay over the chunk along its row, taken after the broadcast
        end_rows = jnp.exp(jnp.broadcast_to(acs[:, Q - 1:Q], (hb, width)))   # [hb, width]
    else:
        b_t = b_ref[...].astype(f32).T                              # [N, Q]
        c = c_ref[...]                                              # [Q, N]
        scores = jnp.dot(c, b_t.astype(dtype), preferred_element_type=f32)   # [Q, Q]
    lower = (jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 0)
             >= jax.lax.broadcasted_iota(jnp.int32, (ROWS, ROWS), 1))
    head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1) // P

    for g in range(hb // per_group):
        lanes = slice(g * width, (g + 1) * width)
        heads = range(g * per_group, (g + 1) * per_group)
        x = x_ref[:, lanes]                                         # [Q, width]
        if per_head:  # the head's own B and C: the same lanes as its x
            b_t = b_ref[:, lanes].astype(f32).T
            c = c_ref[:, lanes]
            scores = jnp.dot(c, b_t.astype(dtype), preferred_element_type=f32)
        # each head's own lanes of x: the group's heads are stacked along the
        # contraction of one product, so no head reads another's lanes
        own = [jnp.where(head_of_lane == j, x, jnp.zeros_like(x)) for j in range(per_group)]
        intra = []
        for r in range(0, Q, ROWS):  # a block of rows reads the columns up to its last
            rows, mixed = slice(r, r + ROWS), []
            for h in heads:
                for k in range(0, r + ROWS, ROWS):
                    cols = slice(k, k + ROWS)
                    diff = acs_col[rows, h:h + 1] - acs[h:h + 1, cols]
                    if k == r:  # the exponent is masked, not the exponential
                        diff = jnp.where(lower, diff, -jnp.inf)
                    decayed = scores[rows, cols] * jnp.exp(diff) * dt[h:h + 1, cols]
                    mixed.append(decayed.astype(dtype))
            intra.append(jnp.dot(jnp.concatenate(mixed, axis=1),
                                 jnp.concatenate([o[:r + ROWS] for o in own], axis=0),
                                 preferred_element_type=f32))
        read, end = (jnp.broadcast_to(v[:, heads[0]:heads[0] + 1], (v.shape[0], width))
                     for v in (reads, ends))
        if per_head:  # a row of a computed block: no broadcast across both at once
            end = end_rows[g:g + 1, :]
        for j, h in enumerate(heads[1:], 1):
            mine = head_of_lane == j
            read = jnp.where(mine, reads[:, h:h + 1], read)
            end = jnp.where(mine, ends[:, h:h + 1], end)
        # the state handed in, read before this group's update
        carried = jnp.dot(c, st_ref[:, lanes].astype(dtype), preferred_element_type=f32)
        y = jnp.concatenate(intra, axis=0) + carried * read + d_ref[:, lanes] * x.astype(f32)
        y_ref[:, lanes] = y.astype(y_ref.dtype)
        # the update: B^T's columns weighted a head at a time (a row, no lane broadcast)
        left = jnp.concatenate([(b_t * weights[h:h + 1, :]).astype(dtype) for h in heads], axis=1)
        st_ref[:, lanes] = end * st_ref[:, lanes] + jnp.dot(
            left, jnp.concatenate(own, axis=0), preferred_element_type=f32)


def _pallas(x, b, c, acs, dt, d_lanes, *, state_size, chunk, b_block, b_col, c_col, per_head,
            interpret):
    """The kernel over ``x``, ``b``, ``c`` ``[batch, Lp, ...]`` (``B`` and
    ``C`` blocks ``b_block`` wide at column block ``b_col(h)`` / ``c_col(h)``
    of head block ``h``), ``acs``, ``dt`` ``[batch, chunks, H, chunk]``
    float32 and ``d_lanes [1, H P]`` float32: ``y [batch, Lp, H P]``."""
    b_, Lp, _ = x.shape
    H = acs.shape[2]
    inner = d_lanes.shape[1]
    P, hb = inner // H, HEADS_PER_STEP
    kernel = functools.partial(_scan_kernel, head_dim=P, chunk=chunk, per_head=per_head)

    def spec(block, index):
        return pl.BlockSpec(block, index, memory_space=pltpu.VMEM)

    with jax.named_scope("kernel_fwd"):
        return pl.pallas_call(
            kernel,
            grid=(b_, H // hb, Lp // chunk),
            in_specs=[
                spec((None, chunk, hb * P), lambda i, h, c: (i, c, h)),
                spec((None, chunk, b_block), lambda i, h, c: (i, c, b_col(h))),
                spec((None, chunk, b_block), lambda i, h, c: (i, c, c_col(h))),
                spec((None, None, hb, chunk), lambda i, h, c: (i, c, h, 0)),
                spec((None, None, hb, chunk), lambda i, h, c: (i, c, h, 0)),
                spec((1, hb * P), lambda i, h, c: (0, h)),
            ],
            out_specs=spec((None, chunk, hb * P), lambda i, h, c: (i, c, h)),
            out_shape=jax.ShapeDtypeStruct((b_, Lp, inner), x.dtype),
            scratch_shapes=[pltpu.VMEM((state_size, hb * P), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "parallel", "arbitrary")),
            interpret=interpret,
            name="ssd_scan_fwd",
        )(x, b, c, acs, dt, d_lanes)


@functools.partial(jax.jit, static_argnames=("state_size", "chunk", "interpret"))
def _scan_call(xBC, acs, dt, d_lanes, *, state_size, chunk, interpret):
    """The kernel over ``xBC [b, Lp, H P + 2 N]``, ``acs``, ``dt`` ``[b,
    chunks, H, chunk]`` float32 and ``d_lanes [1, H P]`` float32: ``y [b, Lp,
    H P]``. A jitted function of its own, so that the layers of a model share
    one trace and one lowering."""
    inner, N = d_lanes.shape[1], state_size
    return _pallas(xBC, xBC, xBC, acs, dt, d_lanes, state_size=N, chunk=chunk, b_block=N,
                   b_col=lambda h: inner // N, c_col=lambda h: inner // N + 1,
                   per_head=False, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def _linear_call(x, b, c, acs, dt, d_lanes, *, chunk, interpret):
    """The kernel with a group a head: ``x``, ``b``, ``c`` ``[batch, Lp, H
    128]``, head ``h``'s ``B`` and ``C`` in the lanes of its ``x``."""
    return _pallas(x, b, c, acs, dt, d_lanes, state_size=LANES, chunk=chunk,
                   b_block=HEADS_PER_STEP * LANES,
                   b_col=lambda h: h, c_col=lambda h: h, per_head=True, interpret=interpret)


def ssd_scan_fwd(xBC, dt, A, D, *, state_size, chunk=256, interpret=False):
    """``xBC [b, L, H P + 2 N]`` (the convolution's output: ``x``, ``B``,
    ``C``), ``dt [b, L, H]`` float32 after the softplus, ``A [H]`` float32,
    ``D [H]`` -> ``y [b, L, H P]`` in ``xBC``'s type, as
    :func:`gigapath_tpu.ops.ssd.ssd_scan` returns it. The widths are those
    :func:`fits` takes; ``L`` need be no multiple of ``chunk``: the tail is
    padded with ``dt = 0``, under which a position neither decays the state
    nor adds to it."""
    b, L, H = dt.shape
    inner = xBC.shape[-1] - 2 * state_size
    Lp = round_up(L, chunk)
    if Lp != L:
        xBC = jnp.pad(xBC, ((0, 0), (0, Lp - L), (0, 0)))
    # [b, chunks, H, chunk]: a layout no producer of dt can take for free, so
    # that the input projection keeps its rows (a transpose to [b, H, Lp]
    # would lay the whole projection out with positions on the lanes)
    dt = jnp.pad(dt.astype(jnp.float32), ((0, 0), (0, Lp - L), (0, 0)))
    dt = dt.reshape(b, Lp // chunk, chunk, H).transpose(0, 1, 3, 2)
    acs = jnp.cumsum(dt * A.astype(jnp.float32)[:, None], axis=-1)
    d_lanes = jnp.repeat(D.astype(jnp.float32), inner // H)[None]
    y = _scan_call(xBC, acs, dt, d_lanes, state_size=state_size, chunk=chunk, interpret=interpret)
    return y[:, :L]


def linear_scan_fwd(x, b, c, A, *, chunk=128, interpret=False):
    """Decayed linear attention, ``ssd.linear_scan``'s kernel tier: ``x``
    (values), ``b`` (keys), ``c`` (queries, scaled) ``[batch, L, H 128]``, ``A
    [H]`` float32 the log of each head's decay; ``dt = 1``, ``D = 0``. Returns
    ``y [batch, L, H 128]`` in ``x``'s type. ``L`` need be no multiple of
    ``chunk``: the padded tail lies after every real position and no real
    output reads it."""
    batch, L, inner = x.shape
    H = A.shape[0]
    Lp = round_up(L, chunk)
    if Lp != L:
        x, b, c = (jnp.pad(a, ((0, 0), (0, Lp - L), (0, 0))) for a in (x, b, c))
    dt = jnp.ones((batch, Lp // chunk, H, chunk), jnp.float32)
    acs = jnp.cumsum(dt * A.astype(jnp.float32)[:, None], axis=-1)
    d_lanes = jnp.zeros((1, inner), jnp.float32)
    y = _linear_call(x, b, c, acs, dt, d_lanes, chunk=chunk, interpret=interpret)
    return y[:, :L]
