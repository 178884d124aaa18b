"""Fine-tune harness: optimizer recipe, schedule, checkpointing, end-to-end CLI.

Covers the reference training stack (``finetune/{main,params,training,utils}.py``)
on synthetic fixtures: layer-decay group construction, warmup-cosine values,
gradient accumulation boundary, freeze-as-optimizer-label, Orbax
checkpoint round-trip + best-score monitor + kill-and-resume, and the full
k-fold CLI writing summary.csv (BASELINE config 4's shape, tiny scale).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pandas as pd
import pytest

from gigapath_tpu.finetune.utils import (
    build_optimizer,
    get_layer_id,
    get_loss_function,
    make_lr_schedule,
    param_labels_lrd,
)
from gigapath_tpu.utils.checkpoint import (
    MonitorScore,
    restore_checkpoint,
    save_checkpoint,
)

D_IN = 16


class TestLayerDecay:
    def test_get_layer_id_mapping(self):
        assert get_layer_id(("slide_encoder", "patch_embed", "proj", "kernel"), 3) == 0
        assert get_layer_id(("slide_encoder", "cls_token"), 3) == 0
        assert get_layer_id(("slide_encoder", "encoder", "layers_1", "ffn"), 3) == 2
        assert get_layer_id(("slide_encoder", "norm", "scale"), 3) == 3
        assert get_layer_id(("classifier", "kernel"), 3) == 3

    def test_labels_and_groups(self):
        params = {
            "slide_encoder": {
                "patch_embed": {"proj": {"kernel": jnp.zeros((4, 4)), "bias": jnp.zeros(4)}},
                "encoder": {"layers_0": {"fc1": {"kernel": jnp.zeros((4, 4))}}},
            },
            "classifier": {"kernel": jnp.zeros((4, 2))},
        }
        labels, groups = param_labels_lrd(params, num_layers=2)
        assert labels["slide_encoder"]["patch_embed"]["proj"]["kernel"] == "layer0_decay"
        assert labels["slide_encoder"]["patch_embed"]["proj"]["bias"] == "layer0_no_decay"
        assert labels["slide_encoder"]["encoder"]["layers_0"]["fc1"]["kernel"] == "layer1_decay"
        assert labels["classifier"]["kernel"] == "layer2_decay"

    def test_deeper_layers_get_larger_updates(self):
        """layer_decay^(num_layers - id): early layers update less."""
        params = {
            "slide_encoder": {
                "patch_embed": {"proj": {"kernel": jnp.ones((4, 4))}},
                "encoder": {"layers_0": {"fc1": {"kernel": jnp.ones((4, 4))}}},
            },
            "classifier": {"kernel": jnp.ones((4, 2))},
        }
        tx = build_optimizer(
            params,
            lr=1.0,
            warmup_epochs=0,
            epochs=1,
            steps_per_epoch=100,
            weight_decay=0.0,
            layer_decay=0.5,
            num_layers=2,
            gc=1,
            lr_scheduler="fixed",
        )
        state = tx.init(params)
        grads = jax.tree.map(jnp.ones_like, params)
        updates, _ = tx.update(grads, state, params)
        u_early = float(
            jnp.abs(updates["slide_encoder"]["patch_embed"]["proj"]["kernel"]).mean()
        )
        u_late = float(jnp.abs(updates["classifier"]["kernel"]).mean())
        # scales: layer0 -> 0.25, layer2 -> 1.0
        assert u_late / u_early == pytest.approx(4.0, rel=0.01)

    def test_freeze_subtree_zeroes_updates(self):
        params = {
            "slide_encoder": {"patch_embed": {"proj": {"kernel": jnp.ones((4, 4))}}},
            "classifier": {"kernel": jnp.ones((4, 2))},
        }
        tx = build_optimizer(
            params,
            lr=1.0,
            warmup_epochs=0,
            epochs=1,
            steps_per_epoch=10,
            layer_decay=1.0,
            num_layers=1,
            gc=1,
            freeze_subtree="slide_encoder",
            lr_scheduler="fixed",
        )
        state = tx.init(params)
        grads = jax.tree.map(jnp.ones_like, params)
        updates, _ = tx.update(grads, state, params)
        assert (
            float(jnp.abs(updates["slide_encoder"]["patch_embed"]["proj"]["kernel"]).sum())
            == 0.0
        )
        assert float(jnp.abs(updates["classifier"]["kernel"]).sum()) > 0

    def test_grad_accumulation_boundary(self):
        params = {"classifier": {"kernel": jnp.ones((2, 2))}}
        tx = build_optimizer(
            params,
            lr=1.0,
            warmup_epochs=0,
            epochs=1,
            steps_per_epoch=10,
            layer_decay=1.0,
            num_layers=1,
            gc=4,
            lr_scheduler="fixed",
        )
        state = tx.init(params)
        grads = jax.tree.map(jnp.ones_like, params)
        for i in range(3):
            updates, state = tx.update(grads, state, params)
            assert float(jnp.abs(updates["classifier"]["kernel"]).sum()) == 0.0
        updates, state = tx.update(grads, state, params)  # 4th: real step
        assert float(jnp.abs(updates["classifier"]["kernel"]).sum()) > 0


class TestSchedule:
    def test_warmup_then_cosine(self):
        sched = make_lr_schedule(
            lr=1.0, min_lr=0.0, warmup_epochs=1, epochs=5, steps_per_epoch=10
        )
        assert float(sched(0)) == 0.0
        assert float(sched(5)) == pytest.approx(0.5)  # mid-warmup
        assert float(sched(10)) == pytest.approx(1.0)  # warmup end
        assert float(sched(50)) == pytest.approx(0.0, abs=1e-6)  # end
        mid = float(sched(30))  # halfway through cosine
        assert mid == pytest.approx(0.5, abs=0.01)

    def test_loss_functions(self, rng):
        ce = get_loss_function({"setting": "multi_class"})
        logits = jnp.asarray(rng.normal(size=(2, 3)), jnp.float32)
        loss = ce(logits, jnp.asarray([0, 2]))
        assert float(loss) > 0
        bce = get_loss_function({"setting": "multi_label"})
        loss2 = bce(logits, jnp.asarray([[1, 0, 1], [0, 1, 0]]))
        assert float(loss2) > 0


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        state = {
            "params": {"w": np.arange(6, dtype=np.float32).reshape(2, 3)},
            "epoch": np.asarray(3),
        }
        path = str(tmp_path / "ckpt")
        save_checkpoint(path, state)
        restored = restore_checkpoint(path)
        np.testing.assert_array_equal(restored["params"]["w"], state["params"]["w"])

    def test_monitor_saves_only_improvements(self, tmp_path):
        monitor = MonitorScore()
        path = str(tmp_path / "best")
        assert monitor(0.5, {"v": np.asarray([1.0])}, path)
        assert not monitor(0.4, {"v": np.asarray([2.0])}, path)
        assert monitor(0.6, {"v": np.asarray([3.0])}, path)
        assert restore_checkpoint(path)["v"][0] == 3.0

    def test_kill_and_resume_reproduces_training(self, rng):
        """Save params+opt_state mid-run; resuming reproduces the same
        trajectory as the uninterrupted run (an earlier review's next step)."""
        import tempfile

        params = {"w": jnp.asarray(rng.normal(size=(4, 4)), jnp.float32)}
        tx = optax.adamw(1e-2)
        x = jnp.asarray(rng.normal(size=(8, 4)), jnp.float32)

        def loss_fn(p):
            return ((x @ p["w"]) ** 2).mean()

        @jax.jit
        def step(p, s):
            loss, g = jax.value_and_grad(loss_fn)(p)
            u, s = tx.update(g, s, p)
            return optax.apply_updates(p, u), s, loss

        # uninterrupted: 6 steps
        p1, s1 = params, tx.init(params)
        for _ in range(6):
            p1, s1, loss_ref = step(p1, s1)

        # interrupted at 3, checkpoint, resume fresh
        p2, s2 = params, tx.init(params)
        for _ in range(3):
            p2, s2, _ = step(p2, s2)
        with tempfile.TemporaryDirectory() as tmp:
            path = os.path.join(tmp, "ckpt")
            save_checkpoint(path, {"params": jax.device_get(p2), "opt_state": jax.device_get(s2)})
            template = {"params": jax.device_get(p2), "opt_state": jax.device_get(s2)}
            restored = restore_checkpoint(path, template)
        p3, s3 = restored["params"], restored["opt_state"]
        for _ in range(3):
            p3, s3, loss_resumed = step(p3, s3)
        np.testing.assert_allclose(float(loss_ref), float(loss_resumed), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(p1["w"]), np.asarray(p3["w"]), atol=1e-6
        )


@pytest.fixture
def finetune_fixture(tmp_path, rng):
    """Synthetic 8-slide h5 dataset + csv + tiny task yaml."""
    import h5py

    root = tmp_path / "h5_files"
    root.mkdir()
    rows = []
    for i in range(8):
        n_tiles = 12 + i
        with h5py.File(root / f"s{i}.h5", "w") as f:
            f.create_dataset(
                "features", data=rng.normal(size=(n_tiles, D_IN)).astype(np.float32)
            )
            f.create_dataset(
                "coords", data=rng.integers(0, 2000, (n_tiles, 2)).astype(np.float32)
            )
        rows.append(
            {"slide_id": f"s{i}.svs", "pat_id": f"p{i}", "label": ["neg", "pos"][i % 2]}
        )
    csv_path = tmp_path / "dataset.csv"
    pd.DataFrame(rows).to_csv(csv_path, index=False)

    yaml_path = tmp_path / "task.yaml"
    yaml_path.write_text(
        "name: toy\nsetting: multi_class\n"
        "label_dict:\n  neg: 0\n  pos: 1\nmax_tiles: 64\nshuffle_tiles: false\n"
    )
    return str(tmp_path), str(csv_path), str(yaml_path), str(root)


def test_finetune_main_end_to_end(finetune_fixture):
    """Two folds of the full CLI on the tiny arch -> summary.csv."""
    from gigapath_tpu.finetune.main import main

    base, csv_path, yaml_path, root = finetune_fixture
    save_dir = os.path.join(base, "out")
    results = main(
        [
            "--task_cfg_path", yaml_path,
            "--dataset_csv", csv_path,
            "--root_path", root,
            "--split_dir", os.path.join(base, "splits"),
            "--save_dir", save_dir,
            "--model_arch", "gigapath_slide_enc_tiny",
            "--input_dim", str(D_IN),
            "--latent_dim", "32",
            "--feat_layer", "1",
            "--folds", "2",
            "--epochs", "2",
            "--warmup_epochs", "1",
            "--gc", "2",
            "--val_r", "0.25",
            "--model_select", "val",
            "--report_to", "jsonl",
            "--dropout", "0.0",
            "--drop_path_rate", "0.0",
        ]
    )
    assert "test_macro_auroc" in results and len(results["test_macro_auroc"]) == 2
    summary = pd.read_csv(
        os.path.join(save_dir, "toy", "eval_toy", "summary.csv")
    )
    assert "val_macro_auroc" in summary.columns
    assert np.isfinite(summary["test_loss"]).all()
