"""Plain reference for the `minicpm_sala` model: the forward pass of a
MiniCPM-SALA stack (``model_type: minicpm_sala``; InfLLM-v2 block-sparse
attention and decayed lightning linear attention, as ``mixer_types`` says)
in float32 ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
one sequence at a time. No kernels, no tiles' lists, no chunks and no carried
state: the selection is made in the open for each block of query rows and
handed to a masked softmax over every key, and lightning attention is taken
in its quadratic form, so it shares nothing with the program. Nothing here
imports the program; the weights are the benchmark's own, read from the
program's bfloat16 tree by its names. ``mode`` is ``lib/reference.py``'s: the
precision of every matrix product (the compressed-key scores, the query-key
products and the weighted sums of values among them), and how the control is
made.

The equations (all norms RMSNorm, eps ``rms_norm_eps``, with a gain;
``c = scale_depth / sqrt(num_hidden_layers)``)::

    h = scale_emb E[ids];  x = RMSNorm(h)
    sparse:    q = RMSNorm_q(x W_q);  k = RMSNorm_k(x W_k);  v = x W_v;   no positions
               Kc[g, j] = mean(k[g, s j : s j + w]);  P[t, g, j] = sum_{h in g} softmax_j(q[t, h] . Kc[g, j] / sqrt(d)),
               j over the units with s j + w - 1 <= t;  Bs[t, g, b] = max P over the visible units overlapping block b;
               forced: blocks < init_blocks and those that hold max(0, t - window + 1) .. t;
               S[t, g] = top-k blocks b <= t // block (lax.top_k);  a = softmax over s <= t in S of q . k / sqrt(d), of v
               h <- h + c ((a * sigmoid(x W_gate)) W_o)
    lightning: q, k = RoPE(RMSNorm_q(x W_q)), RoPE(RMSNorm_k(x W_k)) (rotate-half, rope_theta);  v = x W_v
               o_t,h = sum_{s<=t} lambda_h^(t-s) (q_t,h . k_s,h) v_s,h / sqrt(d),
               log lambda_h = -2^(-8 (h + 1) / H) (1 - l / (num_hidden_layers - 1) + 1e-5)
               h <- h + c ((RMSNorm_o(o) * sigmoid(x W_gate)) W_o)
    h <- h + c W_down(silu(a') * b'),  [a' | b'] = RMSNorm(h) W_in
    logits = RMSNorm(h)[rows] / (hidden_size / dim_model_base) @ W_head

Departures from the published description, each a matter of form: both
mixers are taken a block of query rows at a time against every key (the
sparse one making its own selection for its rows), and the SwiGLU a block of
rows at a time, so that the reference fits beside the program at 65,536
tokens; logits, and each sparse layer's attention before its gate (what the
program's ``core_rows`` holds), are produced for the rows ``positions``
names; only the first
``depth`` layers run; ``W_gate`` and ``W_up`` are one ``[a | b]`` matrix
(``input_linear``), as the program stores them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.lib.reference import mm
from benchmarks.lib.reference_brumby import rope_halfsplit
from benchmarks.lib.reference_lm import gated_mlp, rms_norm

_F32 = jnp.float32
_QUERY_ROWS = 64      # query rows a block of either mixer: [heads, 64, L] float32
_MLP_ROWS = 4096      # rows a block of the SwiGLU: [4096, 2 x intermediate] float32


def log_decay(layer, heads, num_layers):
    """``log lambda [heads]``: MiniMax-01's Lightning Attention schedule."""
    slopes = 2.0 ** (-8.0 * (np.arange(heads, dtype=np.float64) + 1) / heads)
    return -slopes * (1.0 - layer / (num_layers - 1) + 1e-5)


def overlapping_units(L, dims):
    """``(units [nb, n], real [nb, n])``: for each key block the units whose
    windows overlap it (``s j < block (b + 1)`` and ``s j + w > block b``),
    padded with unit 0 where ``real`` is false."""
    w, s, block = dims["kernel_size"], dims["kernel_stride"], dims["block_size"]
    M, nb = (L - w) // s + 1, -(-L // block)
    lists = [list(range(max(0, (block * b - w) // s + 1), min(M, -(-block * (b + 1) // s))))
             for b in range(nb)]
    n = max(1, max(len(u) for u in lists))
    units = np.array([u + [0] * (n - len(u)) for u in lists], np.int32)
    real = np.array([[i < len(u) for i in range(n)] for u in lists])
    return units, real


def select(q_blk, kc, t, dims, mode):
    """The selection for the query rows ``t [rows]`` (``q_blk [rows, H, d]``)
    from the compressed keys ``kc [M, G, d]``: ``[G, rows, nb]`` bool, block
    ``b`` chosen."""
    M, G, d = kc.shape
    H = q_blk.shape[1]
    w, s, block = dims["kernel_size"], dims["kernel_stride"], dims["block_size"]
    units, real = overlapping_units(dims["length"], dims)
    nb = units.shape[0]
    starts = s * jnp.arange(M)
    scores = mm(q_blk.reshape(-1, G, H // G, d).transpose(1, 0, 2, 3).reshape(G, -1, d),
                kc.transpose(1, 2, 0), mode).reshape(G, -1, H // G, M) / np.sqrt(d)
    visible = (starts + w - 1)[None, :] <= t[:, None]                    # [rows, M]
    scores = jnp.where(visible[None, :, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    probs = jnp.where(jnp.isnan(probs), 0.0, probs).sum(axis=2)          # [G, rows, M]
    probs = jnp.where(visible[None], probs, -jnp.inf)
    Bs = jnp.where(jnp.asarray(real), probs[:, :, units], -jnp.inf).max(axis=-1)   # [G, rows, nb]
    b = jnp.arange(nb)
    eligible = b[None, :] <= t[:, None] // block
    first_local = jnp.maximum(t - dims["window_size"] + 1, 0)[:, None] // block
    forced = eligible & ((b[None, :] < dims["init_blocks"]) | (b[None, :] >= first_local))
    ranked = jnp.where(forced, jnp.inf, jnp.where(eligible, Bs, -jnp.inf))
    values, index = jax.lax.top_k(ranked, min(dims["topk"], nb))
    chosen = jax.nn.one_hot(index, nb, dtype=bool) & (values > -jnp.inf)[..., None]
    return chosen.any(axis=-2)


def sparse_attention(q, k, v, dims, mode, block_rows=_QUERY_ROWS):
    """``q [L, H, d]``, ``k``, ``v`` ``[L, G, d]`` -> ``[L, H, d]``, a block
    of query rows at a time, each making its own selection."""
    L, H, d = q.shape
    G = k.shape[1]
    r = H // G
    pad = -L % block_rows
    keys = jnp.arange(L)
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block_rows, H, d)
    kT, vg = k.transpose(1, 2, 0), v.transpose(1, 0, 2)                  # [G, d, L], [G, L, d]
    w, stride = dims["kernel_size"], dims["kernel_stride"]
    M = (L - w) // stride + 1
    windows = stride * jnp.arange(M)[:, None] + jnp.arange(w)
    kc = k[windows].mean(axis=1)                                         # [M, G, d]

    def block(args):
        i, q_blk = args
        t = i * block_rows + jnp.arange(block_rows)
        if L <= dims["dense_len"]:
            chosen = jnp.ones((G, block_rows, -(-L // dims["block_size"])), bool)
        else:
            chosen = select(q_blk, kc, t, dims, mode)
        mask = chosen[:, :, keys // dims["block_size"]] & (keys[None, :] <= t[:, None])
        q_g = q_blk.reshape(block_rows, G, r, d).transpose(1, 2, 0, 3).reshape(G, r * block_rows, d)
        s = mm(q_g, kT, mode).reshape(G, r, block_rows, L) / np.sqrt(d)
        s = jnp.where(mask[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).reshape(G, r * block_rows, L)
        out = mm(p, vg, mode).reshape(G, r, block_rows, d)
        return out.transpose(2, 0, 1, 3).reshape(block_rows, H, d)

    out = jax.lax.map(block, (jnp.arange(qb.shape[0]), qb))
    return out.reshape(-1, H, d)[:L]


def lightning_quadratic(q, k, v, log_lam, mode, block_rows=_QUERY_ROWS):
    """``q``, ``k``, ``v`` ``[L, H, d]``, ``log_lam [H]`` -> ``o [L, H, d]``,
    ``o_t = sum_{s<=t} lambda^(t-s) (q_t . k_s) v_s / sqrt(d)``, a block of
    query rows at a time."""
    L, H, d = q.shape
    pad = -L % block_rows
    keys = jnp.arange(L)
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, block_rows, H, d)
    kT, vh = k.transpose(1, 2, 0), v.transpose(1, 0, 2)                  # [H, d, L], [H, L, d]
    lam = jnp.asarray(log_lam, _F32)

    def block(args):
        i, q_blk = args
        t = i * block_rows + jnp.arange(block_rows)
        gap = (t[:, None] - keys[None, :]).astype(_F32)                  # [rows, L]
        decay = jnp.where(gap >= 0, jnp.exp(lam[:, None, None] * jnp.maximum(gap, 0.0)), 0.0)
        s = mm(q_blk.transpose(1, 0, 2), kT, mode) * decay / np.sqrt(d)  # [H, rows, L]
        return mm(s, vh, mode).transpose(1, 0, 2)                        # [rows, H, d]

    out = jax.lax.map(block, (jnp.arange(qb.shape[0]), qb))
    return out.reshape(-1, H, d)[:L]


def _gate(p, x, o, mode):
    return o * jax.nn.sigmoid(mm(x, p["gate_proj"]["kernel"], mode))


@functools.partial(jax.jit, static_argnames=("layer", "dims", "mode"))
def _mixer(lp, h, rows, *, layer, dims, mode):
    """``(h + c W_o gate(mixer(RMSNorm(h))), the mixer's core output at the
    rows [rows, heads x head_dim])`` for layer ``layer``."""
    with jax.default_matmul_precision("highest"):
        p, eps = lp["self_attn"], dims["eps"]
        L = h.shape[0]
        x = rms_norm(lp["input_layernorm"]["weight"], h, eps)
        if dims["mixers"][layer] == "minicpm4":
            H, G, d = dims["heads"], dims["kv_heads"], dims["head_dim"]
            q = rms_norm(p["q_norm"]["weight"], mm(x, p["q_proj"]["kernel"], mode).reshape(L, H, d), eps)
            k = rms_norm(p["k_norm"]["weight"], mm(x, p["k_proj"]["kernel"], mode).reshape(L, G, d), eps)
            v = mm(x, p["v_proj"]["kernel"], mode).reshape(L, G, d)
            o = sparse_attention(q, k, v, _Dims(dims, length=L), mode).reshape(L, H * d)
            core = o[rows]
        else:
            H, d = dims["lightning_heads"], dims["lightning_head_dim"]
            q = rms_norm(p["q_norm"]["weight"], mm(x, p["q_proj"]["kernel"], mode).reshape(L, H, d), eps)
            k = rms_norm(p["k_norm"]["weight"], mm(x, p["k_proj"]["kernel"], mode).reshape(L, H, d), eps)
            v = mm(x, p["v_proj"]["kernel"], mode).reshape(L, H, d)
            q, k = rope_halfsplit(q, dims["theta"]), rope_halfsplit(k, dims["theta"])
            o = lightning_quadratic(q, k, v, log_decay(layer, H, dims["layers"]), mode)
            core = o.reshape(L, H * d)[rows]
            o = rms_norm(p["o_norm"]["weight"], o.reshape(L, H * d), eps)
        return h + dims["residual"] * mm(_gate(p, x, o, mode), p["o_proj"]["kernel"], mode), core


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def _mlp(lp, h, *, dims, mode):
    """``h + c SwiGLU(RMSNorm(h))``, a block of rows at a time."""
    with jax.default_matmul_precision("highest"):
        L = h.shape[0]
        rows = min(_MLP_ROWS, L)
        pad = -L % rows
        mlp = lp["mlp"]

        def block(h_blk):
            u = rms_norm(lp["post_attention_layernorm"]["weight"], h_blk, dims["eps"])
            return h_blk + dims["residual"] * gated_mlp(
                mlp["input_linear"]["kernel"], mlp["output_linear"]["kernel"], u, mode)

        out = jax.lax.map(block, jnp.pad(h, ((0, pad), (0, 0))).reshape(-1, rows, h.shape[1]))
        return out.reshape(-1, h.shape[1])[:L]


@functools.partial(jax.jit, static_argnames=("eps", "divisor", "mode"))
def _head(kernel, norm_weight, rows, *, eps, divisor, mode):
    with jax.default_matmul_precision("highest"):
        return mm(rms_norm(norm_weight, rows, eps) / divisor, kernel, mode)


class _Dims(dict):
    """The sizes a layer needs, hashable so that ``jit`` can hold them static."""

    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def _dims(sizes) -> _Dims:
    sparse = sizes["sparse_config"]
    layers = int(sizes["num_hidden_layers"])
    return _Dims(eps=float(sizes["rms_norm_eps"]), heads=int(sizes["num_attention_heads"]),
                 kv_heads=int(sizes["num_key_value_heads"]), head_dim=int(sizes["head_dim"]),
                 lightning_heads=int(sizes["lightning_nh"]),
                 lightning_head_dim=int(sizes["lightning_head_dim"]),
                 theta=float(sizes["rope_theta"]), layers=layers,
                 mixers=tuple(sizes["mixer_types"]),
                 residual=float(sizes["scale_depth"]) / np.sqrt(layers),
                 **{key: int(sparse[key]) for key in ("kernel_size", "kernel_stride", "block_size",
                                                      "topk", "init_blocks", "window_size",
                                                      "dense_len")})


def _embed(params, ids, sizes):
    h = params["embed_tokens"]["embedding"][jnp.asarray(ids, jnp.int32)].astype(_F32)
    return h * float(sizes["scale_emb"])


def forward(params, ids, positions, sizes, mode="f32"):
    """One sequence: ``ids [L]`` int, ``positions [P]`` int -> ``(logits [P,
    vocab_size], core_rows [sparse layers, P, heads x head_dim])`` float32 on
    the host: the logits, and each sparse layer's attention (before its
    gate) at the same rows. ``sizes`` is the configuration file (or its tiny
    preset); the selection's sizes are its ``sparse_config``."""
    dims = _dims(sizes)
    h = _embed(params, ids, sizes)
    rows = jnp.asarray(positions, jnp.int32)
    core_rows = []
    for i in range(int(sizes["depth"])):
        lp = params[f"layers_{i}"]
        h, core = _mixer(lp, h, rows, layer=i, dims=dims, mode=mode)
        h = _mlp(lp, h, dims=dims, mode=mode)
        if dims["mixers"][i] == "minicpm4":
            core_rows.append(np.asarray(core))
    logits = np.asarray(_head(params["lm_head"]["kernel"], params["norm"]["weight"], h[rows],
                              eps=dims["eps"],
                              divisor=float(sizes["hidden_size"]) / float(sizes["dim_model_base"]),
                              mode=mode))
    width = dims["heads"] * dims["head_dim"]
    return logits, np.stack(core_rows) if core_rows else np.zeros((0, len(rows), width), np.float32)


def lm_forward(params, ids, positions, sizes, mode="f32"):
    """``forward``'s logits ``[P, vocab_size]``: the signature
    ``benchmarks/systems/lm.py`` calls."""
    return forward(params, ids, positions, sizes, mode)[0]
