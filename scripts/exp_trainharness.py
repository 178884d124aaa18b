#!/usr/bin/env python
"""A/B: where does the in-harness train step's ~4x over the bare step go?

The PANDA-subset harness measured 0.91 s/it at the 8k bucket while the bare
slide-encoder train step (scripts/exp_remat.py) runs 0.22 s.
Suspects named there: dropout threefry, optax.MultiSteps,
layer-decay multi_transform, all-layer outputs. This experiment also
measures the harness's HOST-side costs, which none of those cover: a fresh
[1, 8192, 1536] fp32 batch is shipped host->device every iteration (50 MB)
plus a blocking
float(loss) sync per step (finetune/training.py:257-267).

Device-side variants run interleaved as chained fori_loops (contention
robustness per the repo's measurement discipline); host-side variants run
the real jitted step in a Python loop, timed wall-clock per iteration.

Note on MultiSteps: the chained loop carries only activations, so its
counter stays at the accumulate branch — that IS the steady state (31 of 32
harness steps accumulate; the 32nd adds one inner update, bounded by the
ld_det variant).
"""

import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

N = 8192
B = 1
VALID = 8000  # typical bucket occupancy: triggers the traced-kvlen path


def build(optimizer, dropout: bool):
    """(step, params, opt_state) for the FULL harness model + given optimizer."""
    import optax  # noqa: F401

    from gigapath_tpu.models.classification_head import get_model

    model, params = get_model(
        input_dim=1536, latent_dim=768, feat_layer="11", n_classes=6,
        model_arch="gigapath_slide_enc12l768d", dtype=jnp.bfloat16,
        dropout=0.1, drop_path_rate=0.0, max_wsi_size=250000, tile_size=256,
    )
    opt_state = optimizer.init(params)
    import optax as _ox

    def step(x, params, opt_state, coords, labels, pad_mask, key):
        def loss_fn(p):
            kw = {}
            if dropout:
                kw = dict(deterministic=False, rngs={"dropout": key})
            else:
                kw = dict(deterministic=True)
            logits = model.apply({"params": p}, x, coords, pad_mask=pad_mask, **kw)
            return _ox.softmax_cross_entropy_with_integer_labels(
                logits, labels
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = optimizer.update(grads, opt_state, params)
        params2 = jax.tree.map(lambda p, u: p + u, params, updates)
        return loss, params2, opt_state2

    return step, params, opt_state


def chained(step, params, opt_state, pad_mask, tag):
    """Chain through x with a forced data dependency on the update."""
    from gigapath_tpu.utils.timing import chained_seconds_per_iter

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(B, N, 1536)), jnp.bfloat16)
    coords = jnp.asarray(rng.uniform(0, 250000, (B, N, 2)), jnp.float32)
    labels = jnp.zeros((B,), jnp.int32)
    key = jax.random.PRNGKey(0)

    def chain_step(x, params, opt_state, coords, labels, pad_mask, key):
        loss, params2, opt_state2 = step(
            x, params, opt_state, coords, labels, pad_mask, key
        )
        leaves = sum(
            g.sum().astype(jnp.float32) for g in jax.tree.leaves(params2)
        )
        return x + ((loss + leaves) * 1e-30).astype(x.dtype)

    sec, _ = chained_seconds_per_iter(
        chain_step, x, args=(params, opt_state, coords, labels, pad_mask, key),
        iters_low=2, iters_high=8,
    )
    print(f"{tag:28s} {sec * 1e3:9.1f} ms/step  {B * N / sec:9.0f} tokens/s")
    return sec


def host_loop(step, params, opt_state, pad_mask, mode, iters=8):
    """The real harness pattern: jitted step in a Python loop."""
    rng = np.random.default_rng(0)
    x_np32 = rng.normal(size=(B, N, 1536)).astype(np.float32)
    x_np16 = x_np32.astype(jnp.bfloat16)
    coords_np = rng.uniform(0, 250000, (B, N, 2)).astype(np.float32)
    labels = jnp.zeros((B,), jnp.int32)
    key = jax.random.PRNGKey(0)
    jstep = jax.jit(step)

    x_dev = jnp.asarray(x_np16)
    coords_dev = jnp.asarray(coords_np)
    # warm the compile + one run
    loss, params, opt_state = jstep(
        x_dev, params, opt_state, coords_dev, labels, pad_mask, key
    )
    jax.block_until_ready(loss)

    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        if mode == "device_resident":
            xi, ci = x_dev, coords_dev
        elif mode == "transfer_fp32":
            xi = jnp.asarray(x_np32).astype(jnp.bfloat16)
            ci = jnp.asarray(coords_np)
        elif mode == "transfer_bf16":
            xi = jnp.asarray(x_np16)
            ci = jnp.asarray(coords_np)
        loss, params, opt_state = jstep(
            xi, params, opt_state, coords_dev if mode == "device_resident" else ci,
            labels, pad_mask, key,
        )
        float(loss)  # the harness blocks here every iteration
        times.append(time.perf_counter() - t0)
    sec = float(np.median(times))
    print(f"loop[{mode}]{'':14s} {sec * 1e3:9.1f} ms/it    {B * N / sec:9.0f} tokens/s")
    return sec


def main():
    import argparse

    import optax

    from gigapath_tpu.finetune.utils import build_optimizer

    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="", help="comma list of variant tags to run")
    ap.add_argument("--skip-loops", action="store_true")
    only = ap.parse_args().only
    only = set(only.split(",")) if only else None
    skip_loops = ap.parse_args().skip_loops

    pad = np.zeros((B, N), bool)
    pad[:, :VALID] = True
    pad_mask = jnp.asarray(pad)

    def ld(gc):
        # mirrors training.py's build (12 enc layers + 1)
        probe_model_params = None
        from gigapath_tpu.models.classification_head import get_model

        _, p0 = get_model(
            input_dim=1536, latent_dim=768, feat_layer="11", n_classes=6,
            model_arch="gigapath_slide_enc12l768d", dtype=jnp.bfloat16,
        )
        return build_optimizer(
            p0, lr=2e-3, min_lr=1e-6, warmup_epochs=1, epochs=2,
            steps_per_epoch=4, weight_decay=0.05, layer_decay=0.95,
            num_layers=13, gc=gc, optim="adamw", lr_scheduler="cosine",
        )

    variants = [
        ("adamw_det_nomask", optax.adamw(1e-4), False, None),
        ("adamw_det_padmask", optax.adamw(1e-4), False, pad_mask),
        ("ld_det_padmask", ld(1), False, pad_mask),
        ("ld_ms32_det_padmask", ld(32), False, pad_mask),
        ("ld_ms32_dropout_padmask", ld(32), True, pad_mask),
    ]
    results = {}
    for tag, opt, do, pm in variants:
        if only is not None and tag not in only:
            continue
        step, params, opt_state = build(opt, do)
        results[tag] = chained(step, params, opt_state, pm, tag)
        del params, opt_state

    if not skip_loops:
        # host-side: the full harness step, driven the way training.py drives it
        step, params, opt_state = build(ld(32), True)
        for mode in ("device_resident", "transfer_bf16", "transfer_fp32"):
            results[f"loop_{mode}"] = host_loop(step, params, opt_state, pad_mask, mode)

    if "adamw_det_nomask" in results:
        base = results["adamw_det_nomask"]
        print("\nattribution vs adamw_det_nomask:")
        for tag, sec in results.items():
            print(f"  {tag:28s} {sec / base:6.2f}x")


if __name__ == "__main__":
    main()
