"""Checkpoint save/restore (Orbax).

Counterpart of the reference's ``torch.save(model.state_dict())`` checkpoints
(``finetune/training.py:207-214``, ``finetune/utils.py:348-350``) plus what
the reference lacks (an earlier review's finding): optimizer-state checkpoints and
kill-and-resume. Sharded arrays are handled natively by Orbax — on a mesh the
save/restore round-trips the sharding layout.
"""

from __future__ import annotations

import json
import os
from typing import Any, Dict, Optional

import jax
import numpy as np


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.PyTreeCheckpointer()


def save_checkpoint(path: str, state: Dict[str, Any]) -> None:
    """Save a pytree state dict (e.g. {"params", "opt_state", "epoch"})."""
    path = os.path.abspath(path)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    _checkpointer().save(path, state, force=True)


def restore_checkpoint(path: str, template: Optional[Dict[str, Any]] = None):
    """Restore a state dict; with ``template``, restores into its
    structure/dtypes (required for opt_state namedtuples)."""
    path = os.path.abspath(path)
    if template is not None:
        import orbax.checkpoint as ocp

        return _checkpointer().restore(
            path, restore_args=ocp.checkpoint_utils.construct_restore_args(template),
            item=template,
        )
    return _checkpointer().restore(path)


def checkpoint_exists(path: str) -> bool:
    return os.path.isdir(os.path.abspath(path))


class MonitorScore:
    """Best-score checkpoint monitor (reference ``Monitor_Score``,
    ``finetune/utils.py:327-350``): saves when the score improves.

    The best score is persisted INSIDE the checkpoint state
    (``best_score`` key) AND in a tiny ``<ckpt>.best.json`` sidecar, so
    a resumed run re-arms the monitor instead of starting at None —
    without this, the first (possibly worse) epoch after a resume would
    overwrite the best checkpoint (PR-8 satellite;
    ``tests/test_resilience.py``). The sidecar is what
    :meth:`from_checkpoint` reads: re-arming is one small JSON read, not
    a full Orbax restore of the params pytree just to extract one
    scalar. The in-state copy stays as the durable fallback (older
    checkpoints, a lost sidecar)."""

    def __init__(self, best_score: Optional[float] = None):
        self.best_score = best_score

    @staticmethod
    def _sidecar(ckpt_name: str) -> str:
        return os.path.abspath(str(ckpt_name)) + ".best.json"

    @classmethod
    def from_checkpoint(cls, ckpt_name: str) -> "MonitorScore":
        """Re-arm from a previous run's best checkpoint: the sidecar
        first (O(1)), the checkpoint state as fallback (None — a fresh
        monitor — when both are missing, unreadable, or predate
        persistence)."""
        try:
            with open(cls._sidecar(ckpt_name), encoding="utf-8") as fh:
                return cls(float(json.load(fh)["best_score"]))
        except (OSError, ValueError, KeyError, TypeError):
            pass
        if not checkpoint_exists(ckpt_name):
            return cls()
        try:
            state = restore_checkpoint(ckpt_name)
            score = state.get("best_score") if isinstance(state, dict) else None
            return cls(None if score is None else float(np.asarray(score)))
        except Exception:
            return cls()

    def __call__(self, val_score: float, state: Dict[str, Any], ckpt_name: str) -> bool:
        if self.best_score is None or val_score > self.best_score:
            self.best_score = val_score
            state = dict(state)
            state["best_score"] = np.asarray(float(val_score))
            save_checkpoint(ckpt_name, jax.device_get(state))
            # atomic sidecar write AFTER the checkpoint lands: a crash
            # between the two leaves a stale sidecar pointing at the
            # previous best, never a best.json for a half-written save
            side = self._sidecar(ckpt_name)
            try:
                tmp = f"{side}.tmp-{os.getpid()}"
                with open(tmp, "w", encoding="utf-8") as fh:
                    json.dump({"best_score": float(val_score)}, fh)
                os.replace(tmp, side)
            except OSError:
                pass  # sidecar is an optimization; the state copy holds
            return True
        return False
