"""Fine-tuning CLI: k-fold cross-validation driver.

Parity with reference ``finetune/main.py:13-102``: task-config load,
effective-LR calculation (``lr = blr * batch_size * gc / 256``), patient
stratification split key, per-fold dataset/loader/train, summary.csv with
mean +- std printout.
"""

from __future__ import annotations

import os
import sys
from typing import Optional

import numpy as np

from gigapath_tpu.obs import console


def main(argv: Optional[list] = None) -> dict:
    import pandas as pd

    from gigapath_tpu.data.loader import get_loader
    from gigapath_tpu.data.slide_dataset import SlideDataset
    from gigapath_tpu.data.splits import get_splits
    from gigapath_tpu.finetune.params import get_finetune_params
    from gigapath_tpu.finetune.task_configs.utils import load_task_config
    from gigapath_tpu.finetune.training import train
    from gigapath_tpu.finetune.utils import get_exp_code, seed_everything
    from gigapath_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    args = get_finetune_params(argv)
    console(str(args))

    seed_everything(args.seed)

    console("Loading task configuration from: {}".format(args.task_cfg_path))
    args.task_config = load_task_config(args.task_cfg_path)
    console(str(args.task_config))
    args.task = args.task_config.get("name", "task")

    args.save_dir = os.path.join(args.save_dir, args.task, args.exp_name)
    args.model_code, args.task_code, args.exp_code = get_exp_code(args)
    args.save_dir = os.path.join(args.save_dir, args.exp_code)
    os.makedirs(args.save_dir, exist_ok=True)
    console("Experiment code: {}".format(args.exp_code))
    console("Setting save directory: {}".format(args.save_dir))

    eff_batch_size = args.batch_size * args.gc
    if args.lr is None or args.lr < 0:
        args.lr = args.blr * eff_batch_size / 256
    console("base lr: %.2e" % (args.lr * 256 / eff_batch_size))
    console("actual lr: %.2e" % args.lr)
    console("accumulate grad iterations: %d" % args.gc)
    console("effective batch size: %d" % eff_batch_size)

    args.split_key = "pat_id" if args.pat_strat else "slide_id"

    args.split_dir = (
        os.path.join(args.split_dir, args.task_code)
        if not args.pre_split_dir
        else args.pre_split_dir
    )
    os.makedirs(args.split_dir, exist_ok=True)
    console("Setting split directory: {}".format(args.split_dir))
    dataset = pd.read_csv(args.dataset_csv)

    results: dict = {}
    for fold in range(args.folds):
        fold_dir = os.path.join(args.save_dir, f"fold_{fold}")
        os.makedirs(fold_dir, exist_ok=True)
        train_splits, val_splits, test_splits = get_splits(
            dataset, fold=fold, **vars(args)
        )
        train_data = SlideDataset(
            dataset, args.root_path, train_splits, args.task_config,
            split_key=args.split_key, seed=args.seed,
        )
        val_data = (
            SlideDataset(
                dataset, args.root_path, val_splits, args.task_config,
                split_key=args.split_key, seed=args.seed,
            )
            if len(val_splits) > 0
            else None
        )
        test_data = (
            SlideDataset(
                dataset, args.root_path, test_splits, args.task_config,
                split_key=args.split_key, seed=args.seed,
            )
            if len(test_splits) > 0
            else None
        )
        args.n_classes = train_data.n_classes
        loaders = get_loader(train_data, val_data, test_data, **vars(args))
        val_records, test_records = train(loaders, fold, args)

        records = {"val": val_records, "test": test_records}
        for record_ in records:
            if records[record_] is None:
                continue
            for key in records[record_]:
                if "prob" in key or "label" in key:
                    continue
                key_ = record_ + "_" + key
                results.setdefault(key_, []).append(records[record_][key])

    results_df = pd.DataFrame(results)
    results_df.to_csv(os.path.join(args.save_dir, "summary.csv"), index=False)
    for key in results_df.columns:
        console(
            "{}: {:.4f} +- {:.4f}".format(
                key, np.mean(results_df[key]), np.std(results_df[key])
            )
        )
    console("Results saved in: {}".format(os.path.join(args.save_dir, "summary.csv")))
    console("Done!")
    return results


if __name__ == "__main__":
    main(sys.argv[1:])
