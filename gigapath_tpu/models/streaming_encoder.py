"""Chunk-granular LongNetViT forward — the model half of streaming
chunked prefill.

:class:`StreamingEncoderSession` is the ``LongNetViT`` entry that
accepts an ingest stream instead of a dense ``[B, L, D]`` array: tile
chunks are patch-embedded + positionally embedded the moment they
arrive, layer 0's q/k/v projections and dilated-branch partial folds run
DURING ingest (overlapping stage-1 tile encoding with stage-2 folding —
the disaggregated pipeline's missing piece, ROADMAP item 4), and
``finalize()`` runs the remaining layers chunk-blocked through one
:class:`~gigapath_tpu.ops.streaming_prefill.StreamingPrefillState` per
layer. The residual stream lives as a list of per-chunk blocks from
ingest to readout; the raw tile-embedding sequence ``[B, L, in_chans]``
is never materialized, and the readout (cls row / masked global-pool
mean) folds across blocks by summation.

Layer math is the pure-function mirror of the flax modules the dense
path runs (``architecture/encoder.py`` + ``ops/attention.py`` +
``ops/feedforward.py``), reading the SAME param tree — pre-LN,
q/k/v/out projections, sub-LN on attention output and inside the FFN,
residuals — so the dense ``LongNetViT.__call__`` stays the parity
oracle at fwd 1e-5. :func:`check_streamable` refuses configurations the
mirror does not cover (multiway, MoE, xPos, deepnorm, post-LN, rel-pos
bias) instead of silently diverging; every registry slide-encoder arch
passes.

``feed`` tolerates OUT-OF-ORDER chunks: arrivals ahead of the fold
frontier are held and folded the moment their predecessors land, so the
executed fold sequence — and therefore the result, BIT-exact — is a
pure function of the slide geometry, not of delivery order (the dist
boundary's retransmit/reassignment parity contract extended through the
encoder).

This module is streaming-sanctioned for gigalint GL014: no chunk-axis
reassembly outside the ``*dense_fallback*`` oracle surface.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from gigapath_tpu.ops import pos_embed as pe
from gigapath_tpu.ops.streaming_prefill import (
    StreamingPrefillState,
    chunk_bounds,
)

DEFAULT_CHUNK_TILES = 2048


def prefill_chunk_tiles(default: int = DEFAULT_CHUNK_TILES) -> int:
    """The ``GIGAPATH_PREFILL_CHUNK`` host flag (session-construction
    read, like the dist boundary's ``GIGAPATH_DIST_CHUNK_TILES`` — never
    at trace time): tiles per streaming-prefill chunk."""
    from gigapath_tpu.obs.runlog import env_number

    return int(env_number("GIGAPATH_PREFILL_CHUNK", default))


def chunked_prefill_default() -> bool:
    """The ``GIGAPATH_CHUNKED_PREFILL`` host flag: whether a driver that
    was told nothing else (``inference.py`` without ``--stream``, a dist
    consumer whose plan document is silent) routes slides through the
    streaming session instead of assemble-then-encode. Read by the
    driver, once, before it picks its loop — never at trace time."""
    from gigapath_tpu.ops.common import env_flag

    return env_flag("GIGAPATH_CHUNKED_PREFILL")


def encoder_config(model):
    """The EncoderConfig the dense path would build for ``model`` —
    derived through the same factory so the two paths can never read
    different hyperparameters."""
    from gigapath_tpu.models.longnet import make_longnet_from_name
    from gigapath_tpu.models.slide_encoder import get_optimal_segment_length

    segment_length = model.segment_length or get_optimal_segment_length(
        model.max_wsi_size, model.tile_size
    )
    _, cfg = make_longnet_from_name(
        model.encoder_name,
        dilated_ratio=model.dilated_ratio,
        segment_length=list(segment_length),
        drop_path_rate=model.drop_path_rate,
        dropout=model.dropout,
        dtype=model.dtype,
    )
    return cfg


def check_streamable(cfg) -> None:
    """Raise NotImplementedError for encoder features the streaming
    mirror does not implement. The gate is explicit so an unsupported
    config can never silently produce near-miss numbers."""
    unsupported = []
    if cfg.multiway:
        unsupported.append("multiway")
    if cfg.moe_freq:
        unsupported.append("moe")
    if cfg.xpos_rel_pos:
        unsupported.append("xpos_rel_pos")
    if cfg.deepnorm:
        unsupported.append("deepnorm")
    if not cfg.encoder_normalize_before:
        unsupported.append("post-LN")
    if cfg.rel_pos_buckets or cfg.max_rel_pos:
        unsupported.append("relative_position_bias")
    if cfg.layernorm_embedding:
        unsupported.append("layernorm_embedding")
    if cfg.vocab_size > 0 and not cfg.no_output_layer:
        unsupported.append("output_projection")
    if unsupported:
        raise NotImplementedError(
            "streaming prefill does not cover encoder features "
            f"{unsupported}; use the dense path (the fallback/oracle)"
        )


# ---------------------------------------------------------------------------
# pure-function mirrors of the flax layer math
# ---------------------------------------------------------------------------

def _layer_norm(x: jnp.ndarray, p: Dict[str, jnp.ndarray],
                eps: float) -> jnp.ndarray:
    """flax ``nn.LayerNorm`` mirror (fast-variance form, fp32 stats)."""
    x32 = x.astype(jnp.float32)
    mean = x32.mean(axis=-1, keepdims=True)
    mean2 = (x32 * x32).mean(axis=-1, keepdims=True)
    var = jnp.maximum(mean2 - mean * mean, 0.0)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def _dense(x: jnp.ndarray, p: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    y = x @ p["kernel"].astype(x.dtype)
    if "bias" in p:
        y = y + p["bias"].astype(x.dtype)
    return y


def _embed_block(proj, embeds: jnp.ndarray, coords: jnp.ndarray, *,
                 embed_dim: int, tile_size: int, ngrids: int,
                 dtype) -> jnp.ndarray:
    """[c, in_chans] + [c, 2] -> [1, c, E]: patch embed + positional
    embedding computed from coords (no table, no sequence)."""
    x = embeds[None].astype(dtype)
    x = _dense(x, proj)
    pos = pe.pos_embed_for_coords(embed_dim, coords[None], tile_size, ngrids)
    return x + pos.astype(x.dtype)


def _qkv_block(lp, h_blk: jnp.ndarray, *, num_heads: int,
               eps: float) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Pre-LN + q/k/v projections of one residual block ->
    ``[B, c, H, Dh]`` triples (EncoderLayer + MultiheadAttention entry)."""
    B, c, E = h_blk.shape
    Dh = E // num_heads
    xn = _layer_norm(h_blk, lp["self_attn_layer_norm"], eps)
    sa = lp["self_attn"]
    q = _dense(xn, sa["q_proj"]).reshape(B, c, num_heads, Dh)
    k = _dense(xn, sa["k_proj"]).reshape(B, c, num_heads, Dh)
    v = _dense(xn, sa["v_proj"]).reshape(B, c, num_heads, Dh)
    return q, k, v


def _post_attention_block(lp, h_blk: jnp.ndarray, attn_blk: jnp.ndarray,
                          *, eps: float, subln: bool) -> jnp.ndarray:
    """Everything after the attention core for one block: inner sub-LN,
    out projection, residual, FFN sublayer (fc1 -> fp32 gelu -> sub-LN
    -> fc2), residual. Mirrors EncoderLayer.__call__ at
    deterministic=True (dropout/drop-path no-ops)."""
    B, c, E = h_blk.shape
    sa = lp["self_attn"]
    a = attn_blk.astype(h_blk.dtype).reshape(B, c, E)
    if subln:
        a = _layer_norm(a, sa["inner_attn_ln"], eps)
    a = _dense(a, sa["out_proj"])
    h = h_blk + a

    ffn = lp["ffn"]
    f = _layer_norm(h, lp["final_layer_norm"], eps)
    f = _dense(f, ffn["fc1"])
    f = jax.nn.gelu(f.astype(jnp.float32)).astype(f.dtype)
    if subln:
        f = _layer_norm(f, ffn["ffn_layernorm"], eps)
    f = _dense(f, ffn["fc2"])
    return h + f


# ---------------------------------------------------------------------------
# the session
# ---------------------------------------------------------------------------

class StreamingEncoderSession:
    """One slide's streaming LongNetViT forward.

    ``feed(idx, tile_embeds [c, in_chans], coords [c, 2])`` consumes the
    deterministic chunk plan's chunks (``chunk_bounds(n_tiles,
    chunk_tiles)`` — the same cut the dist boundary ships), any arrival
    order; ``finalize()`` returns the same list of ``[1, embed_dim]``
    outputs as ``LongNetViT.__call__``. The cls token rides as its own
    single-row block at token position 0, so no chunk is ever
    concatenated with anything.
    """

    def __init__(
        self,
        model,
        params,
        n_tiles: int,
        *,
        chunk_tiles: Optional[int] = None,
        all_layer_embed: bool = False,
        dtype: Any = None,
        runlog=None,
    ):
        """``runlog``: optional obs run log — when set, every stage
        executable (embed / qkv / fold / post-attention) is wrapped in
        its own :class:`~gigapath_tpu.obs.watchdog.CompileWatchdog`, so
        per-shape compiles land as ``compile`` events and any retrace on
        a seen shape is flagged unexpected — the same observability
        contract the dense consumer's watched forward has."""
        cfg = encoder_config(model)
        check_streamable(cfg)
        self.model = model
        self.params = params
        self.cfg = cfg
        self.all_layer_embed = bool(all_layer_embed)
        self.dtype = dtype or model.dtype or jnp.float32
        self.n_tiles = int(n_tiles)
        self.chunk_tiles = int(chunk_tiles or prefill_chunk_tiles())
        self.tile_bounds = chunk_bounds(self.n_tiles, self.chunk_tiles)
        # token space: block 0 is the cls token; tile chunk i becomes
        # token block i+1 shifted by one position. Every tile block —
        # including the ragged final chunk — is PADDED to chunk_tiles
        # rows, with ``valid_len`` masking the suffix out of every
        # branch's keys and the readout: middle and tail chunks share
        # ONE block shape, so slides of every length share the same
        # compiled stage executables (the serving claim; the dense
        # oracle does the same with its 128-multiple alignment pad).
        self.token_bounds = ((0, 1),) + tuple(
            (1 + i * self.chunk_tiles, 1 + (i + 1) * self.chunk_tiles)
            for i in range(len(self.tile_bounds))
        )
        self.valid_tokens = 1 + self.n_tiles  # cls + real tiles
        # fold geometry from the ONE factory-built config (cfg), never
        # re-derived by hand — the single-source invariant
        self.segment_lengths = [int(s) for s in cfg.segment_length]
        self.dilated_ratios = [int(r) for r in cfg.dilated_ratio]
        self.num_heads = int(cfg.encoder_attention_heads)
        self.eps = float(cfg.layernorm_eps)
        self.subln = bool(cfg.subln)
        self.depth = int(cfg.encoder_layers)
        # ONE read of the environment per session, never per chunk or
        # per fold: the PipelineFlags ride every fold call as a static arg
        from gigapath_tpu.ops.pallas_dilated import snapshot_flags

        self.fold_flags = snapshot_flags()

        self._embed_fn = jax.jit(
            _embed_block,
            static_argnames=("embed_dim", "tile_size", "ngrids", "dtype"),
        )
        self._qkv_fn = jax.jit(
            _qkv_block, static_argnames=("num_heads", "eps")
        )
        self._post_fn = jax.jit(
            _post_attention_block, static_argnames=("eps", "subln")
        )
        self._fold_fn = None
        if runlog is not None:
            from gigapath_tpu.obs.watchdog import CompileWatchdog
            from gigapath_tpu.ops.streaming_prefill import fold_pair

            # one watchdog per stage: the cache-size retrace probe is
            # per-attached-callable, so stages must not share one
            self._embed_fn = CompileWatchdog(
                "stream.embed", runlog).wrap(self._embed_fn)
            self._qkv_fn = CompileWatchdog(
                "stream.qkv", runlog).wrap(self._qkv_fn)
            self._post_fn = CompileWatchdog(
                "stream.post", runlog).wrap(self._post_fn)

            def fold_key(*args, **kwargs):
                # the fold's branch geometry AND flags are STATIC
                # kwargs: without them in the key, the second branch's
                # legitimate compile would be flagged as a retrace of
                # the first's
                return tuple(
                    (tuple(a.shape), str(a.dtype))
                    for a in args if hasattr(a, "shape")
                ) + (kwargs.get("segment_len"), kwargs.get("ratio"),
                     kwargs.get("flags"))

            self._fold_fn = CompileWatchdog("stream.fold", runlog).wrap(
                jax.jit(
                    fold_pair,
                    static_argnames=("segment_len", "ratio", "flags"),
                ),
                key_fn=fold_key,
            )
        self._h_blocks: List[Optional[jnp.ndarray]] = (
            [None] * len(self.token_bounds)
        )
        self._layer0 = self._new_state()
        self._held: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        self._next_tile_chunk = 0
        # the cls token is resident from the start: fold it immediately
        cls = self.params["cls_token"].astype(self.dtype).reshape(1, 1, -1)
        self._ingest_block(0, cls)

    # -- plumbing -----------------------------------------------------------

    def _new_state(self) -> StreamingPrefillState:
        return StreamingPrefillState(
            self.token_bounds, self.segment_lengths, self.dilated_ratios,
            valid_len=self.valid_tokens, fold_fn=self._fold_fn,
            flags=self.fold_flags,
        )

    def _layer_params(self, depth: int):
        return self.params["encoder"][f"layers_{depth}"]

    def _ingest_block(self, block_idx: int, h_blk: jnp.ndarray) -> None:
        """Store the residual block and fold it into layer 0 — the part
        of the stack that runs DURING ingest."""
        self._h_blocks[block_idx] = h_blk
        q, k, v = self._qkv_fn(
            self._layer_params(0), h_blk,
            num_heads=self.num_heads, eps=self.eps,
        )
        self._layer0.ingest(block_idx, q, k, v)

    # -- the public surface -------------------------------------------------

    @property
    def n_chunks(self) -> int:
        return len(self.tile_bounds)

    def expected_bounds(self, idx: int) -> Tuple[int, int]:
        return self.tile_bounds[idx]

    def feed(self, idx: int, tile_embeds, coords) -> int:
        """Deliver tile chunk ``idx`` (any order; the frontier buffer
        reorders — it holds raw chunks ahead of the frontier, so its
        residency is the delivery reorder window: O(1) for in-order
        producers, degrading toward the dense assembler's footprint
        only in the adversarial first-chunk-arrives-last case; see
        ``ops/streaming_prefill.py`` on bounding the window at the
        transport). Returns how many chunks have been FOLDED so far."""
        idx = int(idx)
        if not 0 <= idx < self.n_chunks:
            raise ValueError(f"chunk {idx} outside plan of {self.n_chunks}")
        a, b = self.tile_bounds[idx]
        tile_embeds = np.asarray(tile_embeds)
        if tile_embeds.shape[0] != b - a:
            raise ValueError(
                f"chunk {idx}: {tile_embeds.shape[0]} rows != tile range "
                f"[{a}, {b})"
            )
        if idx < self._next_tile_chunk or idx in self._held:
            return self._next_tile_chunk  # duplicate: already folded/held
        if coords is None:
            # the dense path's documented coords fallback (EmbeddingChunk
            # carries coords as Optional): zeros collapse the positional
            # signal to one grid cell but never crash or feed NaN grid
            # indices into the positional embedding
            coords = np.zeros((b - a, 2), np.float32)
        coords = np.asarray(coords, np.float32)
        if coords.shape[0] != b - a:
            raise ValueError(
                f"chunk {idx}: {coords.shape[0]} coord rows != tile "
                f"range [{a}, {b})"
            )
        pad = self.chunk_tiles - (b - a)
        if pad:  # ragged final chunk -> the one shared block shape;
            # the padded rows are masked out of every branch's keys
            # (valid_len) and out of the readout
            tile_embeds = np.pad(tile_embeds, ((0, pad), (0, 0)))
            coords = np.pad(coords, ((0, pad), (0, 0)))
        self._held[idx] = (tile_embeds, coords)
        while self._next_tile_chunk in self._held:
            i = self._next_tile_chunk
            embeds_i, coords_i = self._held.pop(i)
            h = self._embed_fn(
                self.params["patch_embed"]["proj"],
                jnp.asarray(embeds_i, jnp.float32),
                jnp.asarray(coords_i, jnp.float32),
                embed_dim=self.model.embed_dim,
                tile_size=self.model.tile_size,
                ngrids=self.model.slide_ngrids,
                dtype=self.dtype,
            )
            self._ingest_block(i + 1, h)
            self._next_tile_chunk += 1
        return self._next_tile_chunk

    def pending(self) -> List[int]:
        """Chunk indices not yet folded (missing or frontier-held)."""
        return [i for i in range(self._next_tile_chunk, self.n_chunks)
                if i not in self._held] + sorted(self._held)

    # -- consumer crash recovery (ISSUE 13) ---------------------------------

    def export_state(self) -> dict:
        """The session's recovery-critical state as a string-keyed
        pytree of host arrays: the ingest frontier, the resident
        per-block residual stream, the frontier-held raw chunks, and the
        layer-0 fold partials (:meth:`StreamingPrefillState.
        export_state`). Saved by the dist consumer through
        ``resilience/checkpoint.py``'s atomic manifest discipline;
        restored into a geometry-identical fresh session, the remaining
        feeds execute the same deterministic fold schedule and the final
        embedding is BIT-exact vs an uninterrupted run."""
        state: dict = {
            "next_tile_chunk": np.int64(self._next_tile_chunk),
        }
        for i, blk in enumerate(self._h_blocks):
            if blk is not None:
                state[f"h_{i}"] = np.asarray(jax.device_get(blk))
        for i, (embeds, coords) in self._held.items():
            state[f"held_{i}"] = {"embeds": np.asarray(embeds),
                                  "coords": np.asarray(coords)}
        state["layer0"] = self._layer0.export_state()
        return state

    def restore_state(self, state: dict) -> None:
        """Inverse of :meth:`export_state` — the session must have been
        constructed with the same (model, n_tiles, chunk_tiles)
        geometry; everything the constructor folded (the cls block) is
        overwritten wholesale by the restored frontier.

        Restored arrays are placed with the LIVE stage executables'
        output sharding (taken from the constructor's own cls-block
        fold): a restored block on the default SingleDeviceSharding
        next to mesh-placed fresh blocks would give every post-resume
        stage call a fresh jit cache key — one silent recompile per
        shape, exactly what the per-stage watchdogs flag."""
        sharding = None
        cls_qkv = getattr(self._layer0, "_qkv", {}).get(0)
        if cls_qkv is not None:
            sharding = getattr(cls_qkv[0], "sharding", None)

        def place(x):
            arr = jnp.asarray(x, self.dtype)
            if sharding is not None:
                try:
                    arr = jax.device_put(arr, sharding)
                except (ValueError, TypeError):
                    pass
            return arr

        self._next_tile_chunk = int(state["next_tile_chunk"])
        self._h_blocks = [None] * len(self.token_bounds)
        self._held = {}
        for key, value in state.items():
            if key.startswith("h_"):
                self._h_blocks[int(key[len("h_"):])] = place(value)
            elif key.startswith("held_"):
                self._held[int(key[len("held_"):])] = (
                    np.asarray(value["embeds"]),
                    np.asarray(value["coords"], np.float32),
                )
        self._layer0.restore_state(state["layer0"], sharding=sharding)

    def complete(self) -> bool:
        return self._next_tile_chunk == self.n_chunks

    def _run_layer(self, depth: int,
                   h_blocks: List[jnp.ndarray],
                   state: Optional[StreamingPrefillState]) -> List[jnp.ndarray]:
        lp = self._layer_params(depth)
        if state is None:
            state = self._new_state()
            for i, h in enumerate(h_blocks):
                state.ingest(i, *self._qkv_fn(
                    lp, h, num_heads=self.num_heads, eps=self.eps,
                ))
        attn_blocks = state.finalize()
        return [
            self._post_fn(lp, h, a, eps=self.eps, subln=self.subln)
            for h, a in zip(h_blocks, attn_blocks)
        ]

    def _readout(self, h_blocks: List[jnp.ndarray]) -> jnp.ndarray:
        """cls-row or global-pool readout + the model norm, folded
        across blocks by summation (never concatenated)."""
        if self.model.global_pool:
            total = 0.0
            count = 0
            for i, blk in enumerate(h_blocks[1:]):  # tiles, cls excluded
                # static per-block valid count: the tail block's padded
                # suffix rows are excluded from the mean, like the dense
                # path's pad_mask pooling
                a, b = self.tile_bounds[i]
                blk = blk[:, : b - a]
                total = total + blk.astype(jnp.float32).sum(axis=1)
                count += b - a
            pooled = total / jnp.maximum(jnp.float32(count), 1.0)
            return _layer_norm(
                pooled.astype(self.dtype), self.params["norm"],
                float(self.model.norm_eps),
            )
        cls_row = h_blocks[0][:, 0]
        return _layer_norm(
            cls_row, self.params["norm"], float(self.model.norm_eps)
        )

    def finalize(self) -> List[jnp.ndarray]:
        """Run the remaining layers chunk-blocked and read out — the
        same output list as ``LongNetViT.__call__(x, coords,
        all_layer_embed=...)``."""
        if not self.complete():
            raise RuntimeError(
                f"finalize with chunks still missing: {self.pending()}"
            )
        h_blocks = [b for b in self._h_blocks]
        assert all(b is not None for b in h_blocks)
        states = [h_blocks] if self.all_layer_embed else []
        h_blocks = self._run_layer(0, h_blocks, self._layer0)
        if self.all_layer_embed:
            states.append(h_blocks)
        for depth in range(1, self.depth):
            h_blocks = self._run_layer(depth, h_blocks, None)
            if self.all_layer_embed:
                states.append(h_blocks)
        if not self.all_layer_embed:
            # encoder_out carries the encoder's final LN; the all-layer
            # states list does not (dense-path parity,
            # architecture/encoder.py encoder_states vs encoder_out)
            final_ln = self.params["encoder"]["layer_norm"]
            states = [[
                _layer_norm(b, final_ln, self.eps) for b in h_blocks
            ]]
        return [self._readout(blocks) for blocks in states]

    # -- anytime embeddings (ROADMAP item 4 / ISSUE 19) ----------------------

    def _truncated_state(self, n_blocks: int,
                         valid_len: int) -> StreamingPrefillState:
        """A fold state over the FIRST ``n_blocks`` token blocks.
        ``total_len`` stays the full slide length so ``_branch_geometry``'s
        ``g = min(sl, L)`` clamp — and therefore the branch schedule and
        the compiled fold executables — is identical to the final pass;
        only the chunk list and the valid-key horizon shrink."""
        return StreamingPrefillState(
            self.token_bounds[:n_blocks], self.segment_lengths,
            self.dilated_ratios, total_len=self.token_bounds[-1][1],
            valid_len=valid_len, fold_fn=self._fold_fn,
            flags=self.fold_flags,
        )

    def peek(self) -> List[jnp.ndarray]:
        """Provisional embeddings from the chunks folded so far — the
        anytime read of the stream. Layer 0 comes straight off the LIVE
        running ``(out, lse)`` partials (:meth:`StreamingPrefillState.
        peek_blocks` — exact attention over the folded keys, nothing
        recomputed, nothing mutated); layers 1+ run chunk-blocked over
        the truncated block list through the SAME stage executables as
        ``finalize`` (same block shapes, same static fold geometry — a
        peek adds zero compiles once the stages are warm). Returns the
        same per-layer embed list shape as :meth:`finalize`; with every
        chunk folded the two are BIT-exact (identical op sequence) —
        the convergence anchor of the ``serve.stream_confidence``
        surface."""
        f = self._next_tile_chunk
        if f < 1:
            raise RuntimeError("peek before any tile chunk folded")
        n_blocks = 1 + f  # cls + folded tile chunks
        valid = 1 + min(self.n_tiles, f * self.chunk_tiles)
        h_blocks = [b for b in self._h_blocks[:n_blocks]]
        assert all(b is not None for b in h_blocks)
        states = [h_blocks] if self.all_layer_embed else []
        lp = self._layer_params(0)
        attn_blocks = self._layer0.peek_blocks()
        h_blocks = [
            self._post_fn(lp, h, a, eps=self.eps, subln=self.subln)
            for h, a in zip(h_blocks, attn_blocks)
        ]
        if self.all_layer_embed:
            states.append(h_blocks)
        for depth in range(1, self.depth):
            lp = self._layer_params(depth)
            state = self._truncated_state(n_blocks, valid)
            for i, h in enumerate(h_blocks):
                state.ingest(i, *self._qkv_fn(
                    lp, h, num_heads=self.num_heads, eps=self.eps,
                ))
            attn_blocks = state.finalize()
            h_blocks = [
                self._post_fn(lp, h, a, eps=self.eps, subln=self.subln)
                for h, a in zip(h_blocks, attn_blocks)
            ]
            if self.all_layer_embed:
                states.append(h_blocks)
        if not self.all_layer_embed:
            final_ln = self.params["encoder"]["layer_norm"]
            states = [[
                _layer_norm(b, final_ln, self.eps) for b in h_blocks
            ]]
        return [self._readout(blocks) for blocks in states]

    def lse_spread(self) -> float:
        """Layer-0 per-branch LSE spread off the live partials — the
        streaming numerics signal attached to ``stream_peek`` events.
        Syncs to host: call at peek cadence, never per fold."""
        return self._layer0.lse_spread()


def embeds_to_outputs(embeds: List) -> Dict[str, np.ndarray]:
    """The ONE encoder-output contract: a session's per-layer embed list
    -> the ``layer_{i}_embed`` / ``last_layer_embed`` dict of
    ``pipeline.run_inference_with_slide_encoder`` (shared by the serve
    streaming session and the pipeline chunk-iterator entry so the
    parity surfaces cannot diverge)."""
    outputs = {
        f"layer_{i}_embed": np.asarray(e, np.float32)
        for i, e in enumerate(embeds)
    }
    outputs["last_layer_embed"] = np.asarray(embeds[-1], np.float32)
    return outputs


def streaming_forward(
    model,
    params,
    tile_embeds,
    coords,
    *,
    chunk_tiles: Optional[int] = None,
    all_layer_embed: bool = False,
) -> List[jnp.ndarray]:
    """Dense-array convenience wrapper over the session — the surface
    the parity tests drive against ``model.apply`` (the oracle). Accepts
    ``[N, in_chans]`` or ``[1, N, in_chans]``."""
    tile_embeds = np.asarray(tile_embeds)
    coords = np.asarray(coords)
    if tile_embeds.ndim == 3:
        assert tile_embeds.shape[0] == 1, "streaming prefill folds B=1 slides"
        tile_embeds, coords = tile_embeds[0], coords[0]
    session = StreamingEncoderSession(
        model, params, tile_embeds.shape[0], chunk_tiles=chunk_tiles,
        all_layer_embed=all_layer_embed,
    )
    for i, (a, b) in enumerate(session.tile_bounds):
        session.feed(i, tile_embeds[a:b], coords[a:b])
    return session.finalize()
