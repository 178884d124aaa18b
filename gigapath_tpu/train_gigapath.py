"""End-to-end training driver over raw slides (replication additions).

Parity with reference ``docker/workspace/prov-gigapath/train_gigapath.py``:
rename raw slide files, tile them (skip-if-processed), extract tile + slide
features to per-slide ``*_features.pt``-style caches (orbax dirs here,
skip-if-cached, ``extract_features:72,128-131``), then train a
ClassificationHead on the cached slide embeddings with optional frozen
encoder (``train_model:205``); ``create_dummy_labels`` scaffolding
(``:356``) mirrors ``create_labels.py``.
"""

from __future__ import annotations

import glob
import os
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from gigapath_tpu.obs import (
    CompileWatchdog,
    Heartbeat,
    console,
    get_ledger,
    get_metrics,
    get_run_log,
    span,
)


def rename_slide_files(data_dir: str, ext: str = ".ndpi") -> List[str]:
    """Strip query-string suffixes from downloaded slide filenames
    (reference ``rename_ndpi_files:24``)."""
    renamed = []
    for name in sorted(os.listdir(data_dir)):
        if "?" in name:
            clean = name.split("?")[0]
            os.rename(os.path.join(data_dir, name), os.path.join(data_dir, clean))
            name = clean
        if name.endswith(ext) or name.endswith(".png"):
            renamed.append(os.path.join(data_dir, name))
    return renamed


def extract_features(
    slide_files: Sequence[str],
    output_dir: str,
    *,
    tile_encoder=None,
    tile_params=None,
    batch_size: int = 128,
    tile_size: int = 256,
) -> List[str]:
    """Tile + encode each slide into ``<slide>_features`` caches, skipping
    existing ones (reference ``extract_features:72`` + ``:128-131``)."""
    from gigapath_tpu.pipeline import (
        run_inference_with_tile_encoder,
        tile_one_slide,
    )
    from gigapath_tpu.utils.checkpoint import checkpoint_exists, save_checkpoint

    if tile_encoder is None:
        from gigapath_tpu.models.tile_encoder import create_tile_encoder, init_params

        tile_encoder, tile_params = create_tile_encoder(dtype=jnp.bfloat16)

    os.makedirs(output_dir, exist_ok=True)
    feature_paths = []
    for slide_file in slide_files:
        slide_id = os.path.splitext(os.path.basename(slide_file))[0]
        out_path = os.path.join(output_dir, f"{slide_id}_features")
        feature_paths.append(out_path)
        if checkpoint_exists(out_path):
            console(f"Skipping {slide_id} - features cached")
            continue
        slide_dir = tile_one_slide(
            slide_file, os.path.join(output_dir, "tiles"), tile_size=tile_size
        )
        tile_paths = sorted(glob.glob(os.path.join(str(slide_dir), "*.png")))
        out = run_inference_with_tile_encoder(
            tile_paths, tile_encoder, tile_params, batch_size=batch_size
        )
        save_checkpoint(
            out_path, {"features": out["tile_embeds"], "coords": out["coords"]}
        )
    return feature_paths


def create_dummy_labels(
    feature_dir: str, output_file: str, num_classes: int = 2
) -> str:
    """Random labels for cached slides (reference ``create_dummy_labels:356``
    / ``create_labels.py:10``)."""
    import pandas as pd

    slide_ids = [
        os.path.basename(p).replace("_features", "")
        for p in sorted(glob.glob(os.path.join(feature_dir, "*_features")))
    ]
    rng = np.random.default_rng(42)
    labels = rng.integers(0, num_classes, size=len(slide_ids))
    df = pd.DataFrame({"slide_id": slide_ids, "label": labels})
    os.makedirs(os.path.dirname(output_file) or ".", exist_ok=True)
    df.to_csv(output_file, index=False)
    console(f"Created labels file: {output_file}")
    console(f"Label distribution: {df['label'].value_counts().to_dict()}")
    return output_file


def _make_train_step(model, tx, *, guard: bool):
    """The jitted train step, built with or without the in-graph
    non-finite guard (:mod:`gigapath_tpu.resilience.guard`). ``guard``
    is a HOST-side construction choice (never traced): the guard-off
    program is byte-identical HLO to the pre-guard step — pinned by
    ``tests/test_resilience.py``."""
    import optax

    def _loss_and_update(params, opt_state, x, c, y, rng):
        def loss_fn(p):
            logits = model.apply({"params": p}, x, c, deterministic=False,
                                 rngs={"dropout": rng})
            return optax.softmax_cross_entropy_with_integer_labels(logits, y).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return loss, grads, optax.apply_updates(params, updates), new_opt

    if not guard:

        @jax.jit
        def step(params, opt_state, x, c, y, rng):
            loss, _, new_params, new_opt = _loss_and_update(
                params, opt_state, x, c, y, rng
            )
            return new_params, new_opt, loss

        return step

    from gigapath_tpu.resilience.guard import guard_update

    @jax.jit
    def step(params, opt_state, x, c, y, rng):
        loss, grads, new_params, new_opt = _loss_and_update(
            params, opt_state, x, c, y, rng
        )
        (new_params, new_opt), skipped = guard_update(
            loss, grads, (params, opt_state), (new_params, new_opt)
        )
        return new_params, new_opt, loss, skipped

    return step


def train_model(
    feature_dir: str,
    labels_file: str,
    output_dir: str,
    *,
    num_epochs: int = 50,
    learning_rate: float = 1e-4,
    freeze_pretrained: bool = True,
    model_arch: str = "gigapath_slide_enc12l768d",
    latent_dim: int = 768,
    feat_layer: str = "11",
    seed: int = 0,
    resume: Optional[str] = None,
    checkpoint_every: int = 0,
    keep_checkpoints: int = 3,
) -> dict:
    """Train a ClassificationHead on cached slide features
    (reference ``train_model:205``).

    Resilience (PR 8): ``checkpoint_every=N`` saves an atomic verified
    full-train-state snapshot (params/opt_state/step/rng) every N steps
    under ``<output_dir>/ckpts/`` (keep-last-``keep_checkpoints``);
    ``resume="auto"`` continues from the newest VALID one, falling back
    past corrupt checkpoints with an ``anomaly`` event — resumption is
    bit-exact (the rng chain and step cursor ride the snapshot, already-
    done steps are skipped without consuming randomness). A SIGTERM
    lands one final emergency checkpoint through the flight recorder's
    chained handler before the process dies. Non-finite losses become
    zero-update skip-steps via the in-graph guard
    (``GIGAPATH_NONFINITE_GUARD``), with rollback to the last
    checkpoint after M consecutive skips."""
    import optax
    import pandas as pd

    from gigapath_tpu.models.classification_head import get_model
    from gigapath_tpu.resilience import (
        ResilientCheckpointer,
        SkipStepMonitor,
        get_chaos,
        nonfinite_guard_enabled,
    )
    from gigapath_tpu.obs.runlog import fail_run
    from gigapath_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint

    labels_df = pd.read_csv(labels_file).set_index("slide_id")
    feats, coords, labels = [], [], []
    for path in sorted(glob.glob(os.path.join(feature_dir, "*_features"))):
        slide_id = os.path.basename(path).replace("_features", "")
        if slide_id not in labels_df.index:
            continue
        state = restore_checkpoint(path)
        feats.append(np.asarray(state["features"], np.float32))
        coords.append(np.asarray(state["coords"], np.float32))
        labels.append(int(labels_df.loc[slide_id, "label"]))
    assert feats, f"no cached features matched {labels_file}"
    n_classes = int(max(labels)) + 1
    input_dim = feats[0].shape[-1]

    model, params = get_model(
        input_dim=input_dim,
        latent_dim=latent_dim,
        feat_layer=feat_layer,
        n_classes=n_classes,
        model_arch=model_arch,
        freeze=freeze_pretrained,
        dtype=jnp.bfloat16,
    )
    from gigapath_tpu.models.classification_head import frozen_param_labels

    if freeze_pretrained:
        tx = optax.multi_transform(
            {"frozen": optax.set_to_zero(), "trainable": optax.adamw(learning_rate)},
            frozen_param_labels(params),
        )
    else:
        tx = optax.adamw(learning_rate)
    opt_state = tx.init(params)

    # host-side construction choices, read once at driver start: the
    # guard flag picks which program gets traced, chaos parses
    # GIGAPATH_CHAOS (NullChaos when unset)
    guard_on = nonfinite_guard_enabled()
    step = _make_train_step(model, tx, guard=guard_on)
    chaos = get_chaos()

    os.makedirs(output_dir, exist_ok=True)
    runlog = get_run_log(
        "train_gigapath", out_dir=output_dir,
        config={"num_epochs": num_epochs, "learning_rate": learning_rate,
                "freeze_pretrained": freeze_pretrained,
                "model_arch": model_arch, "n_classes": n_classes,
                "n_slides": len(feats), "resume": resume,
                "checkpoint_every": checkpoint_every,
                "nonfinite_guard": guard_on},
    )
    # per-slide sequence lengths vary -> one compile per distinct [1, N, D];
    # the watchdog times each first call and flags unexpected retraces,
    # and the perf ledger captures each new shape's compiled artifact
    ledger = get_ledger(runlog)
    watchdog = CompileWatchdog("train_gigapath.step", runlog, ledger=ledger)
    instrumented_step = watchdog.wrap(step)
    # typed metrics (obs/metrics.py): synced step-wall histogram; the
    # final snapshot flushes inside run_end via the registry's closer
    metrics = get_metrics(runlog)
    step_walls = metrics.histogram("train_gigapath.step_wall_s")
    history = []
    # run seed; a fresh per-step dropout key is split off below (a constant
    # key would freeze one dropout mask for the whole run)
    rng = jax.random.PRNGKey(0)

    ckpt = ResilientCheckpointer(
        os.path.join(output_dir, "ckpts"), keep=keep_checkpoints,
        runlog=runlog, chaos=chaos,
    )
    skip_monitor = SkipStepMonitor(runlog)
    template = {
        "params": jax.device_get(params),
        "opt_state": jax.device_get(opt_state),
        "rng": jax.device_get(rng),
        "step": np.asarray(0),
    }
    start_step = 0
    if resume == "auto":
        restored = ckpt.restore_latest(template)
        if restored is not None:
            state, start_step = restored
            params, opt_state = state["params"], state["opt_state"]
            rng = jnp.asarray(state["rng"])
            start_step = int(state["step"])
            runlog.echo(f"[resume] continuing from step {start_step}")

    # emergency SIGTERM checkpoint: device REFERENCES to the last
    # completed step's state (zero per-step cost; device_get happens
    # inside the handler's save), chained through obs/flight.py
    last_state: dict = {"step": start_step, "state": None}

    def _snapshot():
        if last_state["state"] is None:
            return None
        return last_state["step"], last_state["state"]

    ckpt.arm_sigterm_checkpoint(_snapshot)

    def _train_state(step_count):
        return {"params": params, "opt_state": opt_state, "rng": rng,
                "step": np.asarray(int(step_count))}

    try:
        with Heartbeat(runlog, name="train_gigapath") as heartbeat:
            global_step = 0
            for epoch in range(num_epochs):
                total, n_counted = 0.0, 0
                t_epoch = time.time()
                for x, c, y in zip(feats, coords, labels):
                    if global_step < start_step:
                        # resumed past this step: the checkpointed rng
                        # already consumed its split, so skipping whole
                        # (no split here) keeps the chain bit-exact
                        global_step += 1
                        continue
                    rng, step_rng = jax.random.split(rng)
                    fault = chaos.batch_fault(global_step) if chaos else None
                    xb = chaos.apply_batch_fault(fault, x) if fault else x
                    # the fenced span is the honest step clock (GL008):
                    # dur_s covers dispatch AND execution of this step
                    with span("step", runlog, fence=True) as sp:
                        out = instrumented_step(
                            params,
                            opt_state,
                            jnp.asarray(xb[None]),
                            jnp.asarray(c[None]),
                            jnp.asarray([y]),
                            step_rng,
                        )
                        if guard_on:
                            params, opt_state, loss, skipped = out
                        else:
                            params, opt_state, loss = out
                            skipped = 0.0
                        sp.fence(loss)
                    loss_f = float(loss)  # per-slide sync (tiny model)
                    skipped_f = float(skipped)
                    if skipped_f < 0.5:
                        total += loss_f
                        n_counted += 1
                    # observed BEFORE the step event so the event carries
                    # the regime's run length (the anomaly engine's
                    # nonfinite_step detector reports `consecutive`)
                    verdict = None
                    extra = {}
                    if skipped_f >= 0.5:
                        verdict = skip_monitor.observe(
                            global_step, skipped_f
                        )
                        extra = {"nonfinite": True,
                                 "consecutive": skip_monitor.last_consecutive}
                    runlog.step(
                        global_step, wall_s=sp.dur_s,
                        synced=True, epoch=epoch, loss=loss_f, **extra,
                    )
                    if sp.dur_s is not None:
                        step_walls.observe(sp.dur_s)
                    metrics.maybe_flush()
                    if verdict == "rollback":
                        # not a resume: the rollback reports its own
                        # recovery action below
                        rolled = ckpt.restore_latest(
                            template, emit_resume=False
                        )
                        if rolled is not None:
                            state, rb_step = rolled
                            params, opt_state = (
                                state["params"], state["opt_state"]
                            )
                            rng = jnp.asarray(state["rng"])
                            skip_monitor.rollback_performed()
                            runlog.recovery(
                                action="rollback", step=global_step,
                                to_step=rb_step,
                            )
                            runlog.echo(
                                f"[guard] rolled params back to "
                                f"checkpointed step {rb_step}"
                            )
                        else:
                            skip_monitor.rollback_unavailable(global_step)
                    heartbeat.beat(global_step)
                    global_step += 1
                    last_state["step"] = global_step
                    last_state["state"] = _train_state(global_step)
                    if checkpoint_every and global_step % checkpoint_every == 0:
                        ckpt.save(global_step, last_state["state"])
                    if chaos:
                        chaos.maybe_sigterm(global_step - 1)
                history.append(total / max(n_counted, 1))
                epoch_sec = time.time() - t_epoch
                runlog.echo(
                    "Epoch: {}, Loss: {:.4f}, Epoch time: {:.1f}s "
                    "({:.3f} sec/it)".format(
                        epoch, history[-1], epoch_sec,
                        epoch_sec / max(len(feats), 1)
                    ),
                    step=global_step - 1,
                )
        save_checkpoint(os.path.join(output_dir, "model"), {"params": jax.device_get(params)})
    except Exception as e:
        fail_run(
            runlog, "train_gigapath.train_model", e,
            emergency=(
                (lambda: ckpt.save(last_state["step"], last_state["state"]))
                if last_state["state"] is not None else None
            ),
        )
        raise
    finally:
        ckpt.disarm()
    runlog.run_end(
        status="ok", final_loss=history[-1] if history else None,
        compile_seconds_total=watchdog.compile_seconds_total(),
        skipped_steps=skip_monitor.skip_count,
        rollbacks=skip_monitor.rollback_count,
        ledger_path=ledger.path,
    )
    return {"loss_history": history, "n_classes": n_classes}


def main(
    data_dir: str,
    output_dir: str,
    *,
    tile_encoder=None,
    tile_params=None,
    num_classes: int = 2,
    num_epochs: int = 10,
    **train_kwargs,
):
    """Full journey: rename -> tile -> extract -> (dummy) labels -> train
    (reference ``main:387``)."""
    from gigapath_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    slide_files = rename_slide_files(data_dir)
    feature_dir = os.path.join(output_dir, "features")
    extract_features(
        slide_files, feature_dir, tile_encoder=tile_encoder, tile_params=tile_params
    )
    labels_file = os.path.join(output_dir, "labels.csv")
    if not os.path.exists(labels_file):
        create_dummy_labels(feature_dir, labels_file, num_classes)
    return train_model(
        feature_dir,
        labels_file,
        os.path.join(output_dir, "model"),
        num_epochs=num_epochs,
        **train_kwargs,
    )
