"""Slide-level fine-tuning loop.

Parity with reference ``finetune/training.py:130-337``: per-fold training
with layer-decay AdamW, per-iteration cosine warmup, gradient accumulation
(``gc``), per-epoch eval, best-val-AUROC or last-epoch model selection,
checkpoint reload, final test; ``sec/it`` + running mean sequence length
echoed every 20 iterations (``training.py:278-282``); model statistics at
startup (param counts by module type + compiled FLOPs — the jax
``cost_analysis`` replacing thop, ``training.py:23-127``).

TPU shape: one jitted ``train_step(params, opt_state, batch, rng)`` closure;
bf16 activations replace the fp16 GradScaler; batches arrive
bucket-padded from the collate so the step retraces only O(log L) times.

Observability: every run appends schema-versioned JSONL events (step
timings + in-graph loss/grad-norm/param-norm scalars, compile/retrace
accounting via ``CompileWatchdog``, eval metrics, heartbeat/stall
liveness) to a per-run file under ``<save_dir>/fold_k/obs/`` — fold it
into a report with ``scripts/obs_report.py``. Console output goes
through the RunLog echo (one format across drivers, wall time + step
included); ``GIGAPATH_OBS=0`` disables the event stream but keeps the
echo.
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from gigapath_tpu.finetune.metrics import calculate_metrics_with_task_cfg
from gigapath_tpu.finetune.utils import (
    build_optimizer,
    get_loss_function,
    get_records_array,
    log_writer,
    make_writer,
)
from gigapath_tpu.models.classification_head import get_model
from gigapath_tpu.obs import (
    CompileWatchdog,
    Heartbeat,
    NullRunLog,
    get_ledger,
    get_metrics,
    get_run_log,
    span,
)
from gigapath_tpu.obs.numerics import (
    NumericsMonitor,
    numerics_enabled,
    numerics_scalars,
    split_numerics,
)
from gigapath_tpu.obs.runlog import fail_run
from gigapath_tpu.obs.telemetry import step_scalars
from gigapath_tpu.utils.checkpoint import MonitorScore, restore_checkpoint, save_checkpoint


def count_model_statistics(model, params) -> Dict[str, Any]:
    """Param counts by module type + total (reference
    ``count_model_statistics_simple:98``)."""
    leaves = jax.tree_util.tree_flatten_with_path(params)[0]
    total = sum(int(np.prod(p.shape)) for _, p in leaves)
    by_top: Dict[str, int] = {}
    for path, p in leaves:
        top = getattr(path[0], "key", str(path[0]))
        by_top[top] = by_top.get(top, 0) + int(np.prod(p.shape))
    return {"total_params": total, "params_by_module": by_top}


from gigapath_tpu.utils.profiling import compiled_flops  # noqa: F401  (re-export)


def _batch_to_device(batch):
    def dev(x):
        # prefetched batches arrive device-resident — round-tripping them
        # through np.asarray would force a host sync per field
        return x if isinstance(x, jax.Array) else jnp.asarray(np.asarray(x))

    images = dev(batch["imgs"])
    coords = dev(batch["coords"])
    labels = dev(batch["labels"])
    pad_mask = dev(batch["pad_mask"]) if "pad_mask" in batch else None
    return images, coords, labels, pad_mask


def _prefetched(loader, bf16: bool = False):
    """Wrap a host loader so IO + host->device transfer overlap compute.

    Measured at the 8k bucket (scripts/exp_trainharness.py): the fp32
    transfer alone was 0.5 s of the 0.91 s/it harness step vs a 0.21 s
    device step — the dominant train-loop cost, not the optimizer/dropout
    machinery an earlier review suspected. ``bf16`` gates the transfer-halving
    image cast: it must be on exactly when the model runs bf16 — callers
    in this module read ``getattr(args, "bf16", True)``, the SAME
    expression model creation uses, so model dtype and transfer cast can
    never disagree; the bare default here stays False so external callers
    opt in explicitly."""
    from gigapath_tpu.data.loader import DevicePrefetcher

    return DevicePrefetcher(loader, depth=2, bf16_keys=("imgs",) if bf16 else ())


def _obs_config(args) -> dict:
    """JSON-safe slice of the run config for the run_start manifest."""
    return {
        k: v
        for k, v in sorted(vars(args).items())
        if isinstance(v, (str, int, float, bool)) or v is None
    }


def train(dataloader, fold: int, args):
    """Train one fold; returns ``(val_records, test_records)``
    (reference ``train:130``)."""
    train_loader, val_loader, test_loader = dataloader
    writer_dir = os.path.join(args.save_dir, f"fold_{fold}", "tensorboard")
    writer, report_to = make_writer(args.report_to, writer_dir, args)

    fold_dir = os.path.join(args.save_dir, f"fold_{fold}")
    # GIGAPATH_OBS is read HERE, once, at driver start — never at trace
    # time (gigalint GL001): the event stream lands under fold_dir/obs/
    runlog = get_run_log("finetune", out_dir=fold_dir, config=_obs_config(args))
    # loader hardening (data/slide_dataset.py): retry-exhausted sample
    # skips emit `recovery` events (action="data_retry") on THIS run's
    # bus instead of vanishing into console noise
    for loader in (train_loader, val_loader, test_loader):
        dataset = getattr(loader, "dataset", None)
        if hasattr(dataset, "set_runlog"):
            dataset.set_runlog(runlog)

    dtype = jnp.bfloat16 if getattr(args, "bf16", True) else None
    model, params = get_model(
        input_dim=args.input_dim,
        latent_dim=args.latent_dim,
        feat_layer=args.feat_layer,
        n_classes=args.n_classes,
        model_arch=args.model_arch,
        pretrained=args.pretrained,
        freeze=args.freeze,
        global_pool=args.global_pool,
        dtype=dtype,
        dropout=args.dropout,
        drop_path_rate=args.drop_path_rate,
        max_wsi_size=args.max_wsi_size,
        tile_size=args.tile_size,
        checkpoint_activations=getattr(args, "checkpoint_activations", False),
    )
    stats = count_model_statistics(model, params)
    runlog.echo(f"Model statistics: {stats['total_params']:,} params")
    for mod, n in stats["params_by_module"].items():
        runlog.echo(f"  - {mod}: {n:,}")

    # reference: model.slide_encoder.encoder.num_layers + 1 (utils.py:217)
    enc_layers = [
        k for k in params["slide_encoder"]["encoder"] if k.startswith("layers_")
    ]
    num_layers = len(enc_layers) + 1

    steps_per_epoch = max(len(train_loader) / args.gc, 1e-9)
    optimizer = build_optimizer(
        params,
        lr=args.lr,
        min_lr=args.min_lr,
        warmup_epochs=args.warmup_epochs,
        epochs=args.epochs,
        steps_per_epoch=steps_per_epoch,
        weight_decay=args.optim_wd,
        layer_decay=args.layer_decay,
        num_layers=num_layers,
        gc=args.gc,
        optim=args.optim,
        lr_scheduler=args.lr_scheduler,
        freeze_subtree="slide_encoder" if args.freeze else None,
    )
    opt_state = optimizer.init(params)
    loss_fn = get_loss_function(args.task_config)
    ckpt_path = os.path.join(fold_dir, "checkpoint")
    # re-arm the monitor from a previous run's persisted best_score, so
    # a resumed fold's first (possibly worse) epoch cannot overwrite the
    # best checkpoint (PR-8 satellite). Only the "val" selection policy
    # ever consults the monitor — probing for last_epoch runs would pay
    # the fallback's full Orbax restore for a score nothing reads
    if getattr(args, "model_select", "val") == "val":
        monitor = MonitorScore.from_checkpoint(ckpt_path)
        if monitor.best_score is not None:
            runlog.echo(
                f"[resume] best-checkpoint monitor re-armed at "
                f"{monitor.best_score:.4f}"
            )
    else:
        monitor = MonitorScore()

    multi_label = args.task_config.get("setting", "multi_class") == "multi_label"

    def _loss(params, images, coords, labels, pad_mask, rng):
        logits = model.apply(
            {"params": params},
            images,
            coords,
            pad_mask=pad_mask,
            deterministic=False,
            rngs={"dropout": rng},
        )
        labels = labels if multi_label else labels[:, 0]
        return loss_fn(logits, labels)

    # GIGAPATH_NUMERICS is read HERE, once, at driver start (GL001): the
    # Python bool gates the extra reductions at trace time, so the
    # flag-off step lowers to byte-identical HLO and the flag-on step is
    # still one executable across steps (shape-static summaries)
    numerics_on = numerics_enabled()

    @jax.jit
    def train_step(params, opt_state, images, coords, labels, pad_mask, rng):
        loss, grads = jax.value_and_grad(_loss)(
            params, images, coords, labels, pad_mask, rng
        )
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
        # in-graph telemetry: a few extra reductions in the same XLA
        # program, resolved host-side only at existing sync points
        tel = step_scalars(grads=grads, params=params)
        if numerics_on:
            tel.update(numerics_scalars(grads=grads))
        return params, opt_state, loss, tel

    @jax.jit
    def eval_step(params, images, coords, pad_mask):
        return model.apply(
            {"params": params}, images, coords, pad_mask=pad_mask, deterministic=True
        )

    runlog.echo(f"Training on {len(train_loader.dataset)} samples")
    if val_loader is not None:
        runlog.echo(f"Validating on {len(val_loader.dataset)} samples")
    if test_loader is not None:
        runlog.echo(f"Testing on {len(test_loader.dataset)} samples")
    runlog.echo("Training starts!")

    rng = jax.random.PRNGKey(args.seed)
    val_records, test_records = None, None

    # perf ledger: each new bucket's compiled train step lands a
    # compile_profile event (cost/memory analysis for the first bucket,
    # jaxpr fingerprints for the rest) in <fold_dir>/obs/*.ledger.json
    ledger = get_ledger(runlog)
    compile_log = CompileWatchdog("train_step", runlog, fn=train_step,
                                  ledger=ledger)
    # deadline precedence: an explicit args attribute (programmatic
    # callers) wins; else the env knobs (GIGAPATH_OBS_HEARTBEAT_S /
    # GIGAPATH_OBS_STALL_S); else finetune's historical 60/600 — a PANDA
    # fold's biggest bucket legitimately takes minutes per step, so the
    # generic 300 s deadline would call healthy steps stalls (and now:
    # anomalies)
    from gigapath_tpu.obs.heartbeat import env_seconds

    hb_interval = getattr(args, "obs_heartbeat_s", None)
    hb_stall = getattr(args, "obs_stall_s", None)
    heartbeat = Heartbeat(
        runlog,
        interval_s=(
            float(hb_interval) if hb_interval is not None
            else env_seconds("GIGAPATH_OBS_HEARTBEAT_S", 60.0)
        ),
        stall_after_s=(
            float(hb_stall) if hb_stall is not None
            else env_seconds("GIGAPATH_OBS_STALL_S", 600.0)
        ),
        name="finetune",
    )
    try:
        with heartbeat:
            for epoch in range(args.epochs):
                runlog.echo(f"Epoch: {epoch}")
                rng, epoch_rng = jax.random.split(rng)
                with span("epoch", runlog, epoch=epoch):
                    params, opt_state, train_records = train_one_epoch(
                        train_loader, train_step, params, opt_state, epoch,
                        epoch_rng, args, compile_log=compile_log, runlog=runlog,
                        heartbeat=heartbeat,
                    )

                if val_loader is not None:
                    with span("eval", runlog, epoch=epoch):
                        val_records = evaluate(
                            val_loader, eval_step, params, loss_fn, epoch, args,
                            runlog=runlog, heartbeat=heartbeat,
                        )
                    log_dict = {
                        "train_" + k: v
                        for k, v in train_records.items()
                        if "prob" not in k and "label" not in k
                    }
                    log_dict.update(
                        {
                            "val_" + k: v
                            for k, v in val_records.items()
                            if "prob" not in k and "label" not in k
                        }
                    )
                    log_writer(log_dict, epoch, report_to, writer)
                    score = val_records["macro_auroc"]

                if args.model_select == "val" and val_loader is not None:
                    monitor(score, {"params": jax.device_get(params)}, ckpt_path)
                elif args.model_select == "last_epoch" and epoch == args.epochs - 1:
                    save_checkpoint(ckpt_path, {"params": jax.device_get(params)})

            # still inside the heartbeat scope: the final test pass blocks
            # on the device too (fresh eval_step compiles for unseen
            # buckets) and must not be a stall-monitoring blind spot
            template = {"params": jax.device_get(params)}
            if args.model_select == "val" and val_loader is not None:
                # monitor-saved checkpoints carry the persisted
                # best_score; the restore template must match the
                # saved structure
                template["best_score"] = np.asarray(0.0)
            params = restore_checkpoint(ckpt_path, template)["params"]
            with span("test", runlog):
                test_records = evaluate(
                    test_loader, eval_step, params, loss_fn, args.epochs, args,
                    runlog=runlog, heartbeat=heartbeat,
                )

        log_dict = {
            "test_" + k: v
            for k, v in test_records.items()
            if "prob" not in k and "label" not in k
        }
        log_writer(log_dict, fold, report_to, writer)
        if report_to == "wandb":
            writer.finish()
    except Exception as e:
        # the shared failure tail (error event -> flight dump -> emergency
        # checkpoint -> terminal run_end) — one owner for all drivers
        fail_run(
            runlog, "finetune.train", e,
            emergency=lambda: (
                save_checkpoint(
                    os.path.join(fold_dir, "emergency_checkpoint"),
                    {"params": jax.device_get(params)},
                )
                or os.path.join(fold_dir, "emergency_checkpoint")
            ),
        )
        raise

    runlog.run_end(
        status="ok",
        fold=fold,
        test_macro_auroc=float(test_records.get("macro_auroc", float("nan"))),
        compile_seconds_total=compile_log.compile_seconds_total(),
        stalls=heartbeat.stall_count,
        ledger_path=ledger.path,
    )
    return val_records, test_records


def train_one_epoch(
    train_loader, train_step, params, opt_state, epoch, rng, args,
    compile_log: Optional[CompileWatchdog] = None,
    runlog=None,
    heartbeat: Optional[Heartbeat] = None,
):
    """One epoch (reference ``train_one_epoch:223``); per-iteration LR rides
    inside the optimizer schedule."""
    runlog = runlog if runlog is not None else NullRunLog(driver="finetune")
    # typed metrics (attach-once: one registry per run across epochs;
    # the final snapshot flushes inside run_end via the registry's
    # closer). Only the synced 20-iteration walls are observed — they
    # are the device-truth numbers the report already trusts
    metrics = get_metrics(runlog)
    step_walls = metrics.histogram("finetune.step_wall_s")
    numerics = NumericsMonitor(runlog, name="finetune")
    start_time = time.time()
    seq_len = 0
    records = get_records_array(len(train_loader), args.n_classes)
    n_batches = 0
    steps_per_epoch = len(train_loader)
    # Device-side loss accumulator + async dispatch: the loop blocks only
    # on a bucket's first (compiling) step and at the 20-iteration echoes.
    # A per-iteration float(loss) would sync the host to the device every
    # step and serialize the input transfer the prefetcher overlaps.
    loss_sum = None
    tel = None  # latest step's in-graph scalars (device arrays, unsynced)
    t_prev = start_time

    for batch_idx, batch in enumerate(
        # getattr default MUST match model creation above (dtype line in
        # train()): the cast is correct exactly when the model is bf16
        _prefetched(train_loader, bf16=getattr(args, "bf16", True))
    ):
        images, coords, labels, pad_mask = _batch_to_device(batch)
        seq_len += images.shape[1]
        rng, step_rng = jax.random.split(rng)
        bucket = tuple(images.shape[:2])
        global_step = epoch * steps_per_epoch + batch_idx
        new_bucket = compile_log is not None and compile_log.is_new(bucket)
        if new_bucket and loss_sum is not None:
            # drain the async queue first, or every pending step's runtime
            # gets billed to this bucket's "first call" compile number
            jax.block_until_ready(loss_sum)
        t0 = time.time()
        params, opt_state, loss, tel = train_step(
            params, opt_state, images, coords, labels, pad_mask, step_rng
        )
        if new_bucket:
            jax.block_until_ready(loss)  # isolate the compile cost
            compile_log.record(bucket, time.time() - t0)
            # ledger this bucket's compiled artifact (loops driving the
            # is_new/record surface call profile() themselves; wrap()
            # users get it automatically)
            compile_log.profile(
                bucket, train_step, params, opt_state, images, coords,
                labels, pad_mask, step_rng,
            )
        elif compile_log is not None:
            compile_log.record(bucket, None)
        # fp32 accumulation: a few hundred bf16 adds of ~1.x losses round
        # by up to 1.0 once the sum passes 256 (bf16 ulp)
        loss32 = loss.astype(jnp.float32)
        loss_sum = loss32 if loss_sum is None else loss_sum + loss32
        n_batches += 1
        if heartbeat is not None:
            heartbeat.beat(global_step)

        if (batch_idx + 1) % 20 == 0:
            running_loss = float(loss_sum)  # sync point: bounds queue depth
            # timestamp AFTER the drain: the synced step's wall_s carries
            # the queued device work it just waited for — these are the
            # events obs_report calls device truth
            t_now = time.time()
            time_per_it = (t_now - start_time) / (batch_idx + 1)
            # tel's device arrays are materialized by the sync above —
            # reading them here costs no extra round-trip
            scalars = {k: float(np.asarray(v)) for k, v in tel.items()}
            # per-layer numerics (GIGAPATH_NUMERICS) ride the same sync:
            # num.* keys peel off into their own schema'd event
            scalars, num_scalars = split_numerics(scalars)
            runlog.step(
                global_step,
                wall_s=round(t_now - t_prev, 6),
                synced=True,
                epoch=epoch,
                bucket=str(bucket),
                loss=running_loss / (batch_idx + 1),
                sec_per_it=time_per_it,
                seq_len=seq_len / (batch_idx + 1),
                **scalars,
            )
            if num_scalars:
                numerics.emit(global_step, num_scalars)
            step_walls.observe(round(t_now - t_prev, 6))
            metrics.maybe_flush()
            runlog.echo(
                "Epoch: {}, Batch: {}, Loss: {:.4f}, Time: {:.4f} sec/it, "
                "Seq len: {:.1f}, Slide ID: {}".format(
                    epoch,
                    batch_idx,
                    running_loss / (batch_idx + 1),
                    time_per_it,
                    seq_len / (batch_idx + 1),
                    batch["slide_id"][-1] if "slide_id" in batch else "None",
                ),
                step=global_step,
            )
        else:
            # unsynced: wall_s is host dispatch time under async dispatch;
            # the report reads `synced` and treats these accordingly
            t_now = time.time()
            runlog.step(
                global_step,
                wall_s=round(t_now - t_prev, 6),
                synced=bool(new_bucket),
                epoch=epoch,
                bucket=str(bucket),
            )
        t_prev = t_now

    records["loss"] = (
        float(loss_sum) if loss_sum is not None else 0.0
    ) / max(n_batches, 1)
    epoch_sec = time.time() - start_time
    runlog.echo(
        "Epoch: {}, Loss: {:.4f}, Epoch time: {:.1f}s ({:.3f} sec/it)".format(
            epoch, records["loss"], epoch_sec, epoch_sec / max(n_batches, 1)
        ),
        step=epoch * steps_per_epoch + max(n_batches - 1, 0),
    )
    if compile_log is not None and compile_log.first_call_sec:
        runlog.echo(compile_log.summary())
    return params, opt_state, records


def evaluate(loader, eval_step, params, loss_fn, epoch, args, runlog=None,
             heartbeat: Optional[Heartbeat] = None):
    """Eval pass collecting probs/one-hot labels + metrics
    (reference ``evaluate:289``). Records are accumulated as lists so
    retry-exhausted (skipped) samples never leave all-zero rows in the
    metric inputs. Each batch beats the heartbeat (step number untouched):
    a long healthy eval must stay distinguishable from a hung one."""
    runlog = runlog if runlog is not None else NullRunLog(driver="finetune")
    probs, onehots = [], []
    total_loss, n = 0.0, 0
    task_setting = args.task_config.get("setting", "multi_class")
    for batch in _prefetched(loader, bf16=getattr(args, "bf16", True)):
        if heartbeat is not None:
            heartbeat.beat()
        images, coords, labels, pad_mask = _batch_to_device(batch)
        logits = eval_step(params, images, coords, pad_mask)
        logits = jnp.asarray(logits, jnp.float32)
        if task_setting == "multi_label":
            loss = loss_fn(logits, labels)
            probs.append(np.asarray(jax.nn.sigmoid(logits))[0])
            onehots.append(np.asarray(labels, np.float32)[0])
        else:
            loss = loss_fn(logits, labels[:, 0])
            probs.append(np.asarray(jax.nn.softmax(logits, axis=-1))[0])
            one_hot = np.zeros(args.n_classes, np.float32)
            one_hot[int(labels[0, 0])] = 1.0
            onehots.append(one_hot)
        total_loss += float(loss)
        n += 1

    records = get_records_array(n, args.n_classes)
    records["prob"] = np.stack(probs) if probs else records["prob"]
    records["label"] = np.stack(onehots) if onehots else records["label"]
    records.update(
        calculate_metrics_with_task_cfg(
            records["prob"], records["label"], args.task_config
        )
    )
    records["loss"] = total_loss / max(n, 1)

    runlog.eval_event(
        epoch,
        **{
            k: float(v)
            for k, v in records.items()
            if isinstance(v, (int, float, np.floating))
        },
    )
    if task_setting == "multi_label":
        runlog.echo(
            "Epoch: {}, Loss: {:.4f}, Micro AUROC: {:.4f}, Macro AUROC: {:.4f}, "
            "Micro AUPRC: {:.4f}, Macro AUPRC: {:.4f}".format(
                epoch,
                records["loss"],
                records["micro_auroc"],
                records["macro_auroc"],
                records["micro_auprc"],
                records["macro_auprc"],
            )
        )
    else:
        info = "Epoch: {}, Loss: {:.4f}, AUROC: {:.4f}, ACC: {:.4f}, BACC: {:.4f}".format(
            epoch, records["loss"], records["macro_auroc"], records["acc"], records["bacc"]
        )
        for metric in args.task_config.get("add_metrics", []):
            info += ", {}: {:.4f}".format(metric, records[metric])
        runlog.echo(info)
    return records
