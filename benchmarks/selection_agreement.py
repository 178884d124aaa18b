"""How closely the `deepseek_v32` program follows its reference inside the
new mechanism, at the cell's sizes, many seeds in one process.

    python3 benchmarks/selection_agreement.py --seeds 1,2 [--mtp 1 --depth 2] [--tiny]

For each seed: the configuration's weights and one of the traffic's batches;
the program's logits through ``pipeline.run_inference_with_lm`` (and, with
``--mtp 1``, the prediction module's) against the reference's by the cell's
own statistic, row by row; the program's counter beside the reference's
count; and, for the rows whose logits are asked for, the share of each
layer's selected (query, key) pairs that program and reference both select
(the program's selection read from the ``selection`` it sows, the
reference's from ``lib/reference_deepseek_v32.py``). ``--depth`` / ``--mtp``
cut another share of the same configuration (``--mtp 1 --depth 2``: one
dense layer, one expert layer and the module, what fits one chip at
published widths). One JSON line per seed. Not part of a benchmark run.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="dsv32_prefill_b1_16k")
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--depth", type=int)
    ap.add_argument("--mtp", type=int)
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.drivers.closed_loop import row_gaps
    from benchmarks.lib import reference_deepseek_v32 as reference
    from benchmarks.lib import tables, weights_lm
    from benchmarks.systems.lm import System
    from gigapath_tpu import pipeline

    cell = tables.load("workloads", args.workload)
    config = tables.load("configs", cell["config"])
    traffic = tables.load("traffic", cell["traffic"])
    if args.tiny:
        traffic = {**traffic, **traffic["tiny"]}
    sizes = dict(config["tiny"] if args.tiny else config)
    if args.depth is not None:
        sizes["depth"] = args.depth
    if args.mtp is not None:
        sizes["num_nextn_predict_layers"] = args.mtp
    system = System({**config, **sizes, "tiny": sizes}, args.tiny)   # the share asked for, either preset
    model = system.model

    @jax.jit
    def selections(params, ids, positions):
        """The rows ``positions`` names of every layer's selection, stack first."""
        _, state = model.apply({"params": params}, ids, positions, mutable=["intermediates"])
        found = state["intermediates"]
        names = [f"layers_{i}" for i in range(int(sizes["depth"]))] + (
            ["mtp_layer"] if int(sizes["num_nextn_predict_layers"]) else [])
        return jnp.stack([found[name]["self_attn"]["selection"][0][0][positions[0]]
                          for name in names])

    for seed in (int(s) for s in args.seeds.split(",")):
        params = weights_lm.make_weights(system.param_shapes(), seed)
        ids, positions = system.host_batch(np.random.default_rng(seed), {**traffic, "batch": 1})
        out = pipeline.run_inference_with_lm(ids, positions, lm=(model, params))
        chosen = np.asarray(selections(params, jnp.asarray(ids), jnp.asarray(positions))) != 0
        seen = {"selection": [], "selection_rows": positions[0]}
        ref = reference.lm_forward(params, ids[0], positions[0], sizes, seen=seen)
        gaps = row_gaps(out["logits"][0], ref)
        line = {"seed": seed, "tokens": int(ids.shape[1]), "depth": int(sizes["depth"]),
                "mtp": int(sizes["num_nextn_predict_layers"]),
                "embed_gap_max": float(gaps.max()), "embed_gap_mean": float(gaps.mean()),
                "selected_pairs": out["selected_pairs"][:, 0].tolist(),
                "reference_selected_pairs": seen["selected_pairs"],
                "selection_agreement_by_layer": [
                    float((mine & theirs).sum() / theirs.sum())
                    for mine, theirs in zip(chosen, seen["selection"])]}
        if "mtp_logits" in out:
            gaps = row_gaps(out["mtp_logits"][0], seen["mtp_logits"])
            line.update(mtp_gap_max=float(gaps.max()), mtp_gap_mean=float(gaps.mean()))
        print(json.dumps(line), flush=True)
        del params, out, chosen, seen
    return 0


if __name__ == "__main__":
    sys.exit(main())
