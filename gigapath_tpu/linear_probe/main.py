"""Tile-level linear probe (PCam recipe).

Parity with reference ``linear_probe/main.py``: a single linear classifier
on frozen 1536-d tile embeddings, SGD (or Adam) + cosine annealing over
``train_iters`` iterations of an infinitely-cycled loader, eval every
``eval_interval`` (accuracy / weighted-f1 / macro precision+recall / macro
AUROC+AUPRC), best-f1 model selection, ``results.txt`` artifact
(``main.py:65-260``). This is the cheapest path to the PCam AUC-parity
north star (BASELINE config 2).

TPU shape: the whole train step (forward, CE loss, SGD update, cosine LR)
is one jitted function; embeddings are tiny, so batches stream from numpy.
"""

from __future__ import annotations

import argparse
import itertools
import os
import time
from typing import Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from gigapath_tpu.data.pcam import EmbeddingDataset, Processor
from gigapath_tpu.finetune.utils import log_writer, make_writer, seed_everything
from gigapath_tpu.obs import (
    CompileWatchdog,
    Heartbeat,
    console,
    get_ledger,
    get_metrics,
    get_run_log,
    span,
)
from gigapath_tpu.obs.runlog import fail_run
from gigapath_tpu.utils.checkpoint import restore_checkpoint, save_checkpoint


def build_argparser() -> argparse.ArgumentParser:
    parser = argparse.ArgumentParser(description="Linear Probe")
    # Dataset
    parser.add_argument("--dataset_csv", type=str, default="", help="csv with input samples and labels")
    parser.add_argument("--input_path", type=str, default="", help="The input embedding zip")
    parser.add_argument("--embed_dim", type=int, default=1536, help="The dimension of the embeddings")
    # Training
    parser.add_argument("--batch_size", type=int, default=512, help="Batch size")
    parser.add_argument("--train_iters", type=int, default=12500, help="Number of iterations")
    parser.add_argument("--lr", type=float, default=0.01, help="Learning rate")
    parser.add_argument("--min_lr", type=float, default=0.0, help="Minimum learning rate")
    parser.add_argument("--optim", type=str, default="sgd", help="Optimizer")
    parser.add_argument("--momentum", type=float, default=0.0, help="Momentum")
    parser.add_argument("--weight_decay", type=float, default=0.0, help="Weight decay")
    parser.add_argument("--eval_interval", type=int, default=10000, help="Evaluation interval")
    parser.add_argument("--model_select", type=str, default="best", help="Model selection")
    parser.add_argument("--num_workers", type=int, default=10, help="Accepted for compatibility (unused)")
    parser.add_argument("--seed", type=int, default=42, help="Random seed")
    parser.add_argument("--z_score", action="store_true", default=False, help="Use z-score normalization")
    parser.add_argument("--report_to", type=str, default="tensorboard", choices=["tensorboard", "jsonl"])
    # Output
    parser.add_argument("--output_dir", type=str, default="outputs", help="Output directory")
    return parser


def to_onehot(labels: np.ndarray, num_classes: int) -> np.ndarray:
    onehot = np.zeros((labels.shape[0], num_classes))
    onehot[np.arange(labels.shape[0]), labels] = 1
    return onehot


def _batches(
    dataset, batch_size: int, rng: np.random.Generator, infinite: bool
) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
    n = len(dataset)

    def epoch_indices():
        if infinite:
            while True:
                yield rng.integers(0, n, size=batch_size)  # with replacement
        else:
            order = np.arange(n)
            for start in range(0, n, batch_size):
                yield order[start : start + batch_size]

    for idx in epoch_indices():
        embeds, targets = zip(*(dataset[int(i)] for i in idx))
        yield np.stack(embeds).astype(np.float32), np.asarray(targets, np.int64)


def init_linear_probe(embed_dim: int, num_classes: int, seed: int = 0):
    """Params of the single nn.Linear (reference ``LinearProbe:276``)."""
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    bound = 1.0 / np.sqrt(embed_dim)
    return {
        "kernel": jax.random.uniform(k1, (embed_dim, num_classes), jnp.float32, -bound, bound),
        "bias": jax.random.uniform(k2, (num_classes,), jnp.float32, -bound, bound),
    }


def evaluate(params, loader_fn) -> Tuple[float, float, float, float, float, float]:
    """(accuracy, weighted-f1, macro precision, macro recall, macro auroc,
    macro auprc) — reference ``evaluate:204``."""
    from sklearn.metrics import (
        average_precision_score,
        f1_score,
        precision_recall_fscore_support,
        roc_auc_score,
    )

    preds, targets = [], []
    for embed, target in loader_fn():
        logits = np.asarray(embed @ np.asarray(params["kernel"]) + np.asarray(params["bias"]))
        preds.append(logits)
        targets.append(target)
    pred = np.concatenate(preds)
    target = np.concatenate(targets)
    accuracy = float((pred.argmax(1) == target).mean())
    f1 = f1_score(target, pred.argmax(1), average="weighted")
    precision, recall, _, _ = precision_recall_fscore_support(
        target, pred.argmax(1), average="macro", zero_division=0
    )
    auroc = roc_auc_score(to_onehot(target, pred.shape[1]), pred, average="macro")
    auprc = average_precision_score(to_onehot(target, pred.shape[1]), pred, average="macro")
    return accuracy, f1, precision, recall, auroc, auprc


def train(
    params,
    train_dataset,
    val_dataset,
    test_dataset,
    *,
    train_iters: int,
    batch_size: int = 512,
    lr: float = 0.01,
    min_lr: float = 0.0,
    optim: str = "sgd",
    weight_decay: float = 0.0,
    momentum: float = 0.0,
    output_dir: str = "outputs",
    eval_interval: int = 10000,
    model_select: str = "best",
    seed: int = 42,
    report_to: str = "jsonl",
    **kwargs,
):
    """Train the probe; writes best/last checkpoints + results.txt
    (reference ``train:65-201``)."""
    os.makedirs(output_dir, exist_ok=True)

    class _Args:
        exp_code = "linear_probe"

    writer, report_to = make_writer(report_to, os.path.join(output_dir, "tensorboard"), _Args)
    runlog = get_run_log(
        "linear_probe", out_dir=output_dir,
        config={"train_iters": train_iters, "batch_size": batch_size,
                "lr": lr, "min_lr": min_lr, "optim": optim,
                "weight_decay": weight_decay, "momentum": momentum,
                "eval_interval": eval_interval, "seed": seed},
    )

    schedule = optax.cosine_decay_schedule(lr, train_iters, alpha=min_lr / max(lr, 1e-12))
    if optim == "sgd":
        tx = optax.chain(
            optax.add_decayed_weights(weight_decay) if weight_decay else optax.identity(),
            optax.sgd(schedule, momentum=momentum or None),
        )
    elif optim == "adam":
        tx = optax.adamw(schedule, weight_decay=weight_decay)
    else:
        raise ValueError("Invalid optimizer")
    runlog.echo(f"Set the optimizer as {optim}")
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, embed, target):
        def loss_fn(p):
            logits = embed @ p["kernel"] + p["bias"]
            return optax.softmax_cross_entropy_with_integer_labels(logits, target).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    rng = np.random.default_rng(seed)
    train_stream = _batches(train_dataset, batch_size, rng, infinite=True)
    val_loader = lambda: _batches(val_dataset, batch_size, rng, infinite=False)  # noqa: E731
    test_loader = lambda: _batches(test_dataset, batch_size, rng, infinite=False)  # noqa: E731

    ledger = get_ledger(runlog)
    watchdog = CompileWatchdog("linear_probe.step", runlog, ledger=ledger)
    instrumented_step = watchdog.wrap(step)
    runlog.echo("Start training")
    try:
        params, best_f1, f1 = _train_loop(
            params, opt_state, instrumented_step, train_stream, train_iters,
            schedule, eval_interval, val_loader, output_dir, report_to,
            writer, runlog,
        )

        if model_select == "best" and best_f1 > 0:
            val_f1 = best_f1
            params = restore_checkpoint(os.path.join(output_dir, "best_model"))
        else:
            val_f1 = f1
            params = restore_checkpoint(os.path.join(output_dir, "model"))

        accuracy, f1, precision, recall, auroc, auprc = evaluate(params, test_loader)
        runlog.echo(
            f"Test Accuracy: {accuracy} f1: {f1} Precision: {precision} Recall: "
            f"{recall} AUROC: {auroc} AUPRC: {auprc}"
        )
        with open(os.path.join(output_dir, "results.txt"), "w") as f:
            f.write(f"Val f1: {val_f1}\n")
            f.write(f"Test f1: {f1} Test AUROC: {auroc} Test AUPRC: {auprc}\n")
    except Exception as e:
        # a crashed run must still leave a terminal event in its artifact
        # (the shared obs failure tail: error event -> flight dump ->
        # terminal run_end)
        fail_run(runlog, "linear_probe.train", e)
        raise
    runlog.run_end(
        status="ok", val_f1=val_f1, test_f1=f1, test_auroc=auroc,
        test_auprc=auprc,
        compile_seconds_total=watchdog.compile_seconds_total(),
        ledger_path=ledger.path,
    )
    return {"val_f1": val_f1, "test_f1": f1, "test_auroc": auroc, "test_auprc": auprc}


def _train_loop(
    params, opt_state, instrumented_step, train_stream, train_iters,
    schedule, eval_interval, val_loader, output_dir, report_to, writer,
    runlog,
):
    """The heartbeat-monitored iteration loop; returns
    ``(params, best_f1, last_f1)``."""
    best_f1, f1 = 0.0, 0.0
    # typed metrics (attach-once: same registry as the driver's; the
    # final snapshot flushes inside run_end via the registry's closer)
    metrics = get_metrics(runlog)
    step_walls = metrics.histogram("linear_probe.step_wall_s")
    with Heartbeat(runlog, name="linear_probe") as heartbeat:
        t_prev = time.time()
        for i, (embed, target) in enumerate(itertools.islice(train_stream, train_iters)):
            params, opt_state, loss = instrumented_step(
                params, opt_state, jnp.asarray(embed), jnp.asarray(target)
            )
            heartbeat.beat(i)
            if (i + 1) % 10 == 0:
                cur_lr = float(schedule(i))
                t_now = time.time()
                runlog.step(
                    i, wall_s=round(t_now - t_prev, 6), synced=True,
                    loss=float(loss), lr=cur_lr,
                )
                step_walls.observe(round(t_now - t_prev, 6))
                metrics.maybe_flush()
                t_prev = t_now
                runlog.echo(
                    f"Iteration [{i}/{train_iters}]\tLoss: {float(loss)}\tLR: {cur_lr}",
                    step=i,
                )
                log_writer({"Train Loss": float(loss), "Learning Rate": cur_lr}, i, report_to, writer)
            if (i + 1) % eval_interval == 0 or (i + 1) == train_iters:
                runlog.echo("Start evaluating ...")
                with span("eval", runlog, iteration=i):
                    accuracy, f1, precision, recall, auroc, auprc = evaluate(params, val_loader)
                runlog.eval_event(
                    i, accuracy=accuracy, f1=f1, precision=precision,
                    recall=recall, auroc=auroc, auprc=auprc,
                )
                runlog.echo(
                    f"Val [{i}/{train_iters}] Accuracy: {accuracy} f1: {f1} Precision: "
                    f"{precision} Recall: {recall} AUROC: {auroc} AUPRC: {auprc}",
                    step=i,
                )
                log_writer(
                    {
                        "Val Accuracy": accuracy,
                        "Val f1": f1,
                        "Val AUROC": auroc,
                        "Val AUPRC": auprc,
                        "Val Precision": precision,
                        "Val Recall": recall,
                        "Best f1": best_f1,
                    },
                    i,
                    report_to,
                    writer,
                )
                if f1 > best_f1:
                    runlog.echo(f"Best f1 increase from {best_f1} to {f1}")
                    best_f1 = f1
                    save_checkpoint(os.path.join(output_dir, "best_model"), jax.device_get(params))

    save_checkpoint(os.path.join(output_dir, "model"), jax.device_get(params))
    return params, best_f1, f1


def main(argv=None):
    from gigapath_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = build_argparser().parse_args(argv)
    console(str(args))
    seed_everything(args.seed)
    processor = Processor()
    splits = ["train", "val", "test"]
    train_dataset, val_dataset, test_dataset = [
        EmbeddingDataset(
            args.dataset_csv, args.input_path, split=split,
            z_score=args.z_score, processor=processor,
        )
        for split in splits
    ]
    args.num_classes = len(train_dataset.label_dict)
    console(f"Train: {len(train_dataset)}\tVal: {len(val_dataset)}\tTest: {len(test_dataset)}")
    params = init_linear_probe(args.embed_dim, args.num_classes, args.seed)
    return train(params, train_dataset, val_dataset, test_dataset, **vars(args))


if __name__ == "__main__":
    main()
