"""Fine-tuning utilities: layer-decay optimizer, LR schedule, losses, logging.

Parity with reference ``finetune/utils.py``:

- BEiT layer-wise LR decay (``param_groups_lrd:209`` / ``get_layer_id:260``)
  as an ``optax.multi_transform`` over (layer_id, decay) groups;
- per-iteration half-cosine warmup schedule (``adjust_learning_rate:275``);
- gradient accumulation gc=32 via ``optax.MultiSteps`` (the reference's
  manual ``(batch_idx+1) % gc`` stepping, ``training.py:259-273``);
- BCE-with-logits vs CE loss selection (``get_loss_function:305``);
- experiment code / seeding / TB-or-wandb writer switch.

TPU deltas: no GradScaler (bf16 needs none); freezing is an optimizer label
(``optax.set_to_zero``) instead of ``requires_grad`` mutation — this makes
``freeze`` actually consumable (an earlier review's finding).
"""

from __future__ import annotations

import math
import os
import random
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from gigapath_tpu.obs import console


def seed_everything(seed: int = 7) -> None:
    """Host-side seeding (reference ``seed_torch:26``); device randomness in
    jax flows through explicit PRNG keys instead of global state."""
    random.seed(seed)
    os.environ["PYTHONHASHSEED"] = str(seed)
    np.random.seed(seed)


def get_exp_code(args) -> Tuple[str, str, str]:
    """Experiment code (reference ``get_exp_code:43``)."""
    model_code = "eval"
    if len(args.pretrained) > 0:
        model_code += "_pretrained"
    if args.freeze:
        model_code += "_freeze"
    task_code = args.task
    if args.pat_strat:
        task_code += "_pat_strat"
    return model_code, task_code, f"{model_code}_{task_code}"


# --------------------------------------------------------------------------
# layer-wise LR decay


def get_layer_id(path_names, num_layers: int) -> int:
    """flax param path -> BEiT layer id (reference ``get_layer_id:260``)."""
    names = list(path_names)
    if any(n in ("cls_token", "pos_embed") for n in names):
        return 0
    if "patch_embed" in names:
        return 0
    for n in names:
        if n.startswith("layers_"):
            return int(n.split("_")[1]) + 1
    return num_layers


def param_labels_lrd(
    params,
    num_layers: int,
    frozen_subtree: Optional[str] = None,
):
    """Label tree + group definitions for the layer-decay optimizer.

    Returns ``(labels, groups)`` where groups maps label ->
    ``(layer_id, use_weight_decay)``; frozen params get label 'frozen'.
    """
    groups: Dict[str, Tuple[int, bool]] = {}
    flat = jax.tree_util.tree_flatten_with_path(params)[0]

    def one(path, leaf):
        names = [getattr(p, "key", str(p)) for p in path]
        if frozen_subtree and frozen_subtree in names:
            return "frozen"
        layer_id = get_layer_id(names, num_layers)
        use_decay = getattr(leaf, "ndim", 0) != 1
        label = f"layer{layer_id}_{'decay' if use_decay else 'no_decay'}"
        groups[label] = (layer_id, use_decay)
        return label

    labels = [one(path, leaf) for path, leaf in flat]
    labels_tree = jax.tree_util.tree_unflatten(
        jax.tree_util.tree_structure(params), labels
    )
    return labels_tree, groups


def make_lr_schedule(
    lr: float,
    min_lr: float,
    warmup_epochs: float,
    epochs: float,
    steps_per_epoch: float,
    scheduler: str = "cosine",
) -> Callable[[int], float]:
    """Half-cosine with linear warmup, in optimizer steps (the reference
    computes the same curve from fractional epochs, ``utils.py:275-291``)."""

    def schedule(step):
        if scheduler == "fixed":
            return lr
        epoch = step / max(steps_per_epoch, 1e-9)
        warm = lr * epoch / max(warmup_epochs, 1e-9)
        cos = min_lr + (lr - min_lr) * 0.5 * (
            1.0 + jnp.cos(math.pi * (epoch - warmup_epochs) / max(epochs - warmup_epochs, 1e-9))
        )
        return jnp.where(epoch < warmup_epochs, warm, cos)

    return schedule


def build_optimizer(
    params,
    *,
    lr: float,
    min_lr: float = 1e-6,
    warmup_epochs: float = 1,
    epochs: float = 5,
    steps_per_epoch: float = 1,
    weight_decay: float = 0.05,
    layer_decay: float = 0.95,
    num_layers: int,
    gc: int = 1,
    optim: str = "adamw",
    lr_scheduler: str = "cosine",
    freeze_subtree: Optional[str] = None,
) -> optax.GradientTransformation:
    """The full reference recipe as one optax transformation:
    AdamW + per-(layer, decay) groups + per-step cosine + MultiSteps(gc)."""
    labels, groups = param_labels_lrd(params, num_layers, freeze_subtree)
    layer_scales = {
        i: layer_decay ** (num_layers - i) for i in range(num_layers + 1)
    }

    transforms: Dict[str, optax.GradientTransformation] = {}
    for label, (layer_id, use_decay) in groups.items():
        scale = layer_scales[layer_id]
        sched = make_lr_schedule(
            lr * scale, min_lr * scale, warmup_epochs, epochs, steps_per_epoch,
            lr_scheduler,
        )
        wd = weight_decay if use_decay else 0.0
        if optim == "adamw":
            transforms[label] = optax.adamw(sched, weight_decay=wd)
        else:
            transforms[label] = optax.adam(sched)
    transforms["frozen"] = optax.set_to_zero()

    tx = optax.multi_transform(transforms, labels)
    if gc > 1:
        tx = optax.MultiSteps(tx, every_k_schedule=gc)
    return tx


# --------------------------------------------------------------------------
# losses / records / logging


def get_loss_function(task_config: dict) -> Callable:
    """(logits, labels) -> scalar loss (reference ``get_loss_function:305``)."""
    setting = task_config.get("setting", "multi_class")
    if setting == "multi_label":

        def loss_fn(logits, labels):
            return optax.sigmoid_binary_cross_entropy(
                logits, labels.astype(jnp.float32)
            ).mean()

        return loss_fn
    if setting in ("multi_class", "binary"):

        def loss_fn(logits, labels):
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels.astype(jnp.int32)
            ).mean()

        return loss_fn
    raise NotImplementedError(setting)


def get_records_array(record_len: int, n_classes: int) -> dict:
    return {
        "prob": np.zeros((record_len, n_classes), np.float32),
        "label": np.zeros((record_len, n_classes), np.float32),
        "loss": 0.0,
    }


def log_writer(log_dict: dict, step: int, report_to: str = "tensorboard", writer=None):
    """Scalar logging switch (reference ``log_writer:353``); adds a
    dependency-free 'jsonl' sink."""
    if report_to == "tensorboard":
        for k, v in log_dict.items():
            writer.add_scalar(k, v, step)
    elif report_to == "wandb":
        writer.log(log_dict, step=step)
    elif report_to == "jsonl":
        import json

        writer.write(json.dumps({"step": step, **{k: float(v) for k, v in log_dict.items()}}) + "\n")
        writer.flush()
    else:
        raise NotImplementedError(report_to)


def make_writer(report_to: str, writer_dir: str, args=None):
    """Construct the writer for ``report_to`` (reference
    ``training.py:138-150``); falls back to jsonl when tensorboard is not
    installed."""
    os.makedirs(writer_dir, exist_ok=True)
    if report_to == "wandb":
        import wandb

        wandb.init(project=args.exp_code, config=vars(args))
        return wandb, "wandb"
    if report_to == "tensorboard":
        try:
            from torch.utils import tensorboard

            return tensorboard.SummaryWriter(writer_dir, flush_secs=15), "tensorboard"
        except ImportError:
            console("tensorboard unavailable; logging scalars to metrics.jsonl")
    return open(os.path.join(writer_dir, "metrics.jsonl"), "a"), "jsonl"
