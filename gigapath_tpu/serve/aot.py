"""Per-bucket AOT executable cache with persisted compiled artifacts.

The serving invariant this module owns: **an executable per bucket,
compiled at most once per process, and ideally zero times** — a warm
restart loads the persisted artifact instead of retracing (ROADMAP item
1's cold-start acceptance). Three tiers, checked in order:

1. **in-memory**: the executable already built this process;
2. **artifact**: a persisted ``jax.experimental.serialize_executable``
   payload under ``artifact_dir``, keyed by an environment fingerprint
   (jax version, backend, input signature, caller identity, the
   attention kernels' ``PipelineFlags`` snapshot) so a stale artifact
   from another jax build, model shape or kernel tier can never be
   executed — any mismatch or load failure falls through to a fresh
   compile;
3. **compile**: ``jit(forward, donate_argnums=(1, 2)).lower(...).compile()``
   over ``jax.ShapeDtypeStruct`` inputs (no dummy arrays are ever
   materialized), then persisted best-effort for the next process.

Params ride as a runtime argument (only their shapes are baked in), so
one artifact serves every checkpoint of the same architecture. The
per-request buffers — embeds and coords — are MARKED donated; params
and the key-padding mask are not (params are reused every call, the
mask is noise-sized). Donation only materializes when an output can
alias the ``[B, N, D]`` input (embedding-shaped outputs); for a
logits-shaped forward XLA finds no aliasable output and ignores it,
logging one harmless "donated buffers were not usable" warning per
bucket compile — expected, not a defect.

Observability: compiles are filed with the serving
:class:`~gigapath_tpu.obs.watchdog.CompileWatchdog` through its
``is_new``/``record`` surface, with this cache's :meth:`_cache_size`
standing in for the jit cache (AOT compiles never touch the jit call
cache, so the watchdog's usual probe would be blind here) — cache
growth on an already-seen bucket is flagged as an unexpected retrace
exactly like a jit-cache retrace would be. The perf ledger adopts the
already-compiled executable (``adopt_compiled``: cost/memory analysis
off the existing artifact, fingerprint from one extra trace, ZERO extra
XLA compiles — pinned by tests/test_serve.py's XLA-layer compile
counts).
"""

from __future__ import annotations

import hashlib
import os
import pickle
import time
from typing import Any, Callable, Dict, Optional, Tuple

ARTIFACT_SCHEMA_VERSION = 1


def _param_signature(params: Any) -> str:
    """Stable signature over a param pytree's leaf shapes/dtypes — the
    facts an executable bakes in (values ride at call time)."""
    import jax

    leaves = jax.tree_util.tree_leaves(params)
    h = hashlib.sha256()
    h.update(str(len(leaves)).encode())
    for leaf in leaves:
        h.update(str(getattr(leaf, "shape", ())).encode())
        h.update(str(getattr(leaf, "dtype", "")).encode())
    return h.hexdigest()[:16]


class AotExecutableCache:
    """Bucketed AOT executables for ``forward(params, embeds, coords,
    pad_mask)`` (embeds ``[B, N, D]`` f32, coords ``[B, N, 2]`` f32,
    mask ``[B, N]`` bool, True = valid)."""

    def __init__(self, forward: Callable, params: Any, *,
                 feature_dim: int, artifact_dir: Optional[str] = None,
                 identity: str = "", name: str = "serve.forward",
                 runlog=None, watchdog=None, ledger=None,
                 donate: bool = True):
        import jax

        from gigapath_tpu.obs.runlog import NullRunLog

        self.name = name
        self.params = params
        self.feature_dim = int(feature_dim)
        self.artifact_dir = artifact_dir
        self.identity = identity
        self.runlog = runlog if runlog is not None else NullRunLog()
        self.watchdog = watchdog
        self.ledger = ledger
        self._forward = forward
        self._jit = jax.jit(
            forward, donate_argnums=(1, 2) if donate else ()
        )
        self._param_sig = _param_signature(params)
        # the FULL attention-kernel flag snapshot participates in the
        # artifact identity: a forward built under one tier (ring,
        # stream fusion, ...) must never be satisfied by a persisted
        # executable of another. The code signature usually
        # catches this too, but an untraceable forward degrades to
        # shapes-only — the flag fingerprint is the belt under that
        # suspender, and a NamedTuple repr covers every current and
        # future field without hand-picking. One host-side snapshot at
        # construction, the PipelineFlags convention.
        from gigapath_tpu.ops.pallas_dilated import snapshot_flags

        self._flags_sig = repr(snapshot_flags())
        self._code_sig: Optional[str] = None  # lazy; see _code_signature
        self._executables: Dict[Tuple[int, int], Callable] = {}
        # provenance per key: "compiled" | "artifact"
        self.sources: Dict[Tuple[int, int], str] = {}
        self.compile_seconds: Dict[Tuple[int, int], float] = {}
        if self.watchdog is not None:
            # the watchdog's cache-size probe points HERE: AOT compiles
            # bypass the jit call cache, so compiled-executable count is
            # the honest retrace signal for the serving path
            self.watchdog.attach(self)

    # -- watchdog cache-size surface (mirrors jitted fn._cache_size) ------
    def _cache_size(self) -> int:
        return sum(1 for s in self.sources.values() if s == "compiled")

    @property
    def compiled_count(self) -> int:
        return self._cache_size()

    @property
    def loaded_count(self) -> int:
        return sum(1 for s in self.sources.values() if s == "artifact")

    # -- shapes -----------------------------------------------------------
    def _abstract_inputs(self, capacity: int, bucket_n: int):
        import jax
        import jax.numpy as jnp

        sds = jax.ShapeDtypeStruct
        return (
            sds((capacity, bucket_n, self.feature_dim), jnp.float32),
            sds((capacity, bucket_n, 2), jnp.float32),
            sds((capacity, bucket_n), jnp.bool_),
        )

    def _execution_devices(self) -> list:
        """The devices every executable of this cache runs on: where the
        params live (the jit compiles for its arguments' devices), in
        mesh order when they are sharded over one."""
        import jax

        sharding = jax.tree_util.tree_leaves(self.params)[0].sharding
        mesh = getattr(sharding, "mesh", None)
        if mesh is not None:
            return list(mesh.devices.flat)
        return sorted(sharding.device_set, key=lambda d: d.id)

    # -- artifact persistence ---------------------------------------------
    def _code_signature(self) -> str:
        """Identity for the forward's CODE, not just its shapes: the
        jaxpr at one canonical shape ``[1, 128, D]`` (128 = the
        encoder's pad quantum; the shape is fixed so every process of
        the same code computes the same signature regardless of which
        bucket it serves first). A model-code fix that keeps the arch
        name and param shapes — e.g. a masking correction — changes the
        jaxpr and therefore invalidates persisted artifacts, where a
        shapes-only fingerprint would silently serve pre-fix outputs on
        every warm restart. One abstract trace per process, ZERO XLA
        compiles (the compile-count pins stay intact); an untraceable
        forward degrades to the shapes-only fingerprint with a warning."""
        if self._code_sig is None:
            import jax

            try:
                jaxpr = jax.make_jaxpr(self._forward)(
                    self.params, *self._abstract_inputs(1, 128)
                )
                self._code_sig = hashlib.sha256(
                    str(jaxpr).encode()
                ).hexdigest()[:16]
            except Exception as e:
                self.runlog.echo(
                    f"[serve] forward not abstractly traceable at the "
                    f"canonical shape ({type(e).__name__}: {e}); artifact "
                    "fingerprints fall back to shapes-only (stale CODE "
                    "will not be detected)"
                )
                self._code_sig = "no-code-sig"
        return self._code_sig

    def _fingerprint(self, capacity: int, bucket_n: int) -> str:
        import jax

        h = hashlib.sha256()
        for part in (
            str(ARTIFACT_SCHEMA_VERSION), jax.__version__,
            jax.default_backend(), self.identity, self._param_sig,
            self._code_signature(), self._flags_sig,
            f"{capacity}x{bucket_n}x{self.feature_dim}",
        ):
            h.update(part.encode())
            h.update(b"|")
        return h.hexdigest()[:16]

    def artifact_path(self, capacity: int, bucket_n: int) -> Optional[str]:
        if not self.artifact_dir:
            return None
        return os.path.join(
            self.artifact_dir,
            f"{self.name}-{capacity}x{bucket_n}"
            f"-{self._fingerprint(capacity, bucket_n)}.aot",
        )

    def _try_load(self, path: Optional[str], capacity: int,
                  bucket_n: int) -> Optional[Callable]:
        """Deserialize a persisted executable; None on ANY mismatch or
        failure (a stale artifact must fall through to a compile, never
        crash or mis-execute)."""
        if path is None or not os.path.exists(path):
            return None
        import jax
        from jax.experimental import serialize_executable

        try:
            with open(path, "rb") as fh:
                doc = pickle.load(fh)
            meta = doc["meta"]
            if (
                meta["v"] != ARTIFACT_SCHEMA_VERSION
                or meta["jax_version"] != jax.__version__
                or meta["backend"] != jax.default_backend()
                or meta["fingerprint"] != self._fingerprint(capacity, bucket_n)
            ):
                return None
            # pin the reload to the devices the executable was compiled
            # for: with none given, deserialize_and_load spans EVERY local
            # device of the backend, and a one-device executable then
            # refuses its arguments on any host that has more than one
            return serialize_executable.deserialize_and_load(
                doc["payload"], doc["in_tree"], doc["out_tree"],
                execution_devices=self._execution_devices(),
            )
        except Exception as e:
            self.runlog.echo(
                f"[serve] artifact load failed for bucket "
                f"{capacity}x{bucket_n} ({type(e).__name__}: {e}); "
                "recompiling"
            )
            return None

    def _persist(self, path: Optional[str], compiled, capacity: int,
                 bucket_n: int) -> None:
        """Best-effort: serving must not depend on a writable disk."""
        if path is None:
            return
        import jax
        from jax.experimental import serialize_executable

        try:
            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled
            )
            doc = {
                "meta": {
                    "v": ARTIFACT_SCHEMA_VERSION,
                    "jax_version": jax.__version__,
                    "backend": jax.default_backend(),
                    "fingerprint": self._fingerprint(capacity, bucket_n),
                    "name": self.name,
                    "shape": [capacity, bucket_n, self.feature_dim],
                },
                "payload": payload,
                "in_tree": in_tree,
                "out_tree": out_tree,
            }
            os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as fh:
                pickle.dump(doc, fh)
            os.replace(tmp, path)  # atomic: a killed write leaves no torn artifact
        except Exception as e:
            self.runlog.echo(
                f"[serve] artifact persist failed for bucket "
                f"{capacity}x{bucket_n} ({type(e).__name__}: {e}); "
                "serving continues uncached"
            )

    # -- the three-tier lookup --------------------------------------------
    def executable(self, capacity: int, bucket_n: int) -> Callable:
        """The executable for ``[capacity, bucket_n, feature_dim]``
        batches: in-memory, else artifact load, else compile+persist."""
        key = (int(capacity), int(bucket_n))
        exe = self._executables.get(key)
        if exe is not None:
            return exe

        path = self.artifact_path(*key)
        loaded = self._try_load(path, *key)
        if loaded is not None:
            self._executables[key] = loaded
            self.sources[key] = "artifact"
            if self.watchdog is not None:
                self.watchdog.mark_preloaded(key)
            self.runlog.echo(
                f"[serve] bucket {key[0]}x{key[1]}: loaded persisted "
                f"executable ({os.path.basename(path)}) — no compile"
            )
            return loaded

        import jax

        abstract = self._abstract_inputs(*key)
        t0 = time.time()
        compiled = self._jit.lower(self.params, *abstract).compile()
        seconds = time.time() - t0
        self._executables[key] = compiled
        self.sources[key] = "compiled"
        self.compile_seconds[key] = seconds
        if self.watchdog is not None:
            # files the compile event; cache growth on a seen key would
            # be flagged as an unexpected retrace
            self.watchdog.record(key, seconds)
        if self.ledger is not None:
            self.ledger.adopt_compiled(
                self.name, key, compiled, self._forward,
                self.params, *abstract,
            )
        self._persist(path, compiled, *key)
        return compiled

    def __call__(self, embeds, coords, mask):
        """Dispatch one assembled batch; shapes pick the executable."""
        key = (int(embeds.shape[0]), int(embeds.shape[1]))
        known = key in self._executables
        exe = self.executable(*key)
        if self.watchdog is not None and known:
            # steady dispatch on an already-materialized executable;
            # first sights were filed by executable() (compile) or
            # mark_preloaded (artifact load)
            self.watchdog.record(key, None)
        return exe(self.params, embeds, coords, mask)
