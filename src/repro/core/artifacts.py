"""Content-addressed artifact store for the staged pipeline.

The ApproxPilot flow (Fig. 1) produces a chain of expensive artifacts —
pruned library, labeled dataset, trained surrogate params, inference
engine, Pareto front — and the monolithic `pipeline.run()` used to rebuild
every one of them on every invocation. This module gives each stage a
content-addressed cache slot:

* **Keys** are a stable hash of the *governing config slice*: the stage
  name plus exactly the fields of `PipelineConfig` (and upstream keys)
  that determine the stage's output. Two runs that differ only in, say,
  ``dse_budget`` share the dataset and training artifacts; changing
  ``n_samples`` invalidates the dataset key and everything downstream.
* **Disk tier** (`root` given): picklable artifacts (datasets, trained
  params, DSE results) persist under ``<root>/<key>.pkl`` and survive the
  process — a resumed sweep or a `validate_pareto` call in a later
  session reuses them.
* **Memory tier** (always on): every artifact, including unpicklable ones
  (the `SurrogateEngine` holds jitted closures), is memoized in-process.
  A store with ``root=None`` is memory-only.
* **Stats** (`StoreStats`): per-stage hit/miss counters, asserted by the
  cache-resume tests and surfaced in ``PipelineResult.metrics["store"]``.

JAX pytree leaves are converted to numpy before hitting the disk tier
(`_to_numpy_tree`), so cached params are device-independent; consumers
(`models.predict`, the engine) re-device them lazily via `jnp.asarray`.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pickle
import tempfile
import threading
from dataclasses import dataclass, field
from pathlib import Path
from typing import Any, Callable, Dict, Optional, Tuple


def _canonical(obj: Any) -> Any:
    """Reduce an object to a deterministic, JSON-serializable structure."""
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return {"__dc__": type(obj).__name__,
                **{f.name: _canonical(getattr(obj, f.name))
                   for f in dataclasses.fields(obj)}}
    if isinstance(obj, dict):
        return {str(k): _canonical(v) for k, v in sorted(obj.items(),
                                                         key=lambda kv:
                                                         str(kv[0]))}
    if isinstance(obj, (list, tuple)):
        return [_canonical(v) for v in obj]
    if isinstance(obj, (str, int, float, bool)) or obj is None:
        return obj
    if hasattr(obj, "item"):                     # numpy / jax scalars
        return obj.item()
    # refuse rather than fall back to repr(): default reprs embed memory
    # addresses, which would silently give every process a different key
    # (a cache that never hits across runs)
    raise TypeError(
        f"cache-key spec contains a non-canonicalizable value of type "
        f"{type(obj).__name__}: {obj!r}")


def stable_hash(obj: Any, n_hex: int = 16) -> str:
    """Deterministic content hash of a (nested) config structure."""
    blob = json.dumps(_canonical(obj), sort_keys=True,
                      separators=(",", ":")).encode()
    return hashlib.sha256(blob).hexdigest()[:n_hex]


@dataclass
class StoreStats:
    """Per-stage cache counters (`hits[stage]`, `misses[stage]`) plus the
    ordered event log the cache-resume tests assert on.

    Thread-safe: `record` holds an internal lock, so the dict
    read-modify-write (``d[stage] = d.get(stage, 0) + 1``) cannot lose
    counts when many serving sessions hit one resident store; `as_dict`
    snapshots both dicts under the same lock.

    ``quarantines`` lists the keys whose disk pickle was found corrupt
    and renamed aside (`ArtifactStore.get`) — a non-empty list after a
    crash is the fingerprint of a torn write by an OLD store version or
    external file damage, never of the store's own atomic writer."""
    hits: Dict[str, int] = field(default_factory=dict)
    misses: Dict[str, int] = field(default_factory=dict)
    events: list = field(default_factory=list)   # (stage, "hit"|"miss", key)
    quarantines: list = field(default_factory=list)   # corrupt-pickle keys

    def __post_init__(self):
        self._lock = threading.Lock()

    def record(self, stage: str, hit: bool, key: str) -> None:
        with self._lock:
            d = self.hits if hit else self.misses
            d[stage] = d.get(stage, 0) + 1
            self.events.append((stage, "hit" if hit else "miss", key))

    def record_quarantine(self, key: str) -> None:
        with self._lock:
            self.quarantines.append(key)

    def as_dict(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {"hits": dict(self.hits), "misses": dict(self.misses),
                    "quarantines": list(self.quarantines)}


# the checkout root (src/repro/core/artifacts.py -> three levels up)
DEFAULT_COMPILE_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    The pipeline compiles the same handful of shapes in every process
    (bucketed engine chunks, the training scan, the labeling model); with
    the cache on, a later process loads those executables instead of
    recompiling them. Each entry point (``chip_smoke.py``, the benchmarks,
    ``examples/quickstart.py``, ``repro.launch.serve``) calls this once at
    start-up; library code never does.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    this sets no other directory. Otherwise the cache lives at one fixed
    path inside the checkout, `DEFAULT_COMPILE_CACHE` (git-ignored): a
    directory whose name changed from run to run would never hit. The
    min compile-time / entry-size thresholds are zeroed so the small
    executables of this repo are cached too. Errors propagate: a cache
    that cannot be set up is a failure, not a silent cold run.
    """
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(DEFAULT_COMPILE_CACHE)
        Path(path).mkdir(parents=True, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def _to_numpy_tree(obj: Any) -> Any:
    """jax.Array leaves -> numpy (device-independent pickles)."""
    import jax
    import numpy as np

    def one(x):
        return np.asarray(x) if isinstance(x, jax.Array) else x
    try:
        return jax.tree.map(one, obj)
    except Exception:                            # non-pytree artifact
        return obj


class ArtifactStore:
    """Two-tier (memory + optional disk) content-addressed artifact cache.

    >>> store = ArtifactStore("/tmp/approxpilot-cache")
    >>> key = store.key("dataset", {"app": "sobel", "n_samples": 500})
    >>> ds = store.get_or_build("dataset", key, lambda: expensive_build())

    ``get_or_build`` is the only entry point the pipeline stages use; the
    lower-level ``get``/``put``/``has`` are exposed for tools and tests.
    """

    def __init__(self, root: Optional[str] = None):
        self.root = Path(root) if root is not None else None
        if self.root is not None:
            self.root.mkdir(parents=True, exist_ok=True)
        self._memory: Dict[str, Any] = {}
        # last-write wall-clock timestamp per memory-tier key (same time
        # domain as disk mtimes), for `gc_checkpoints`; disk-only entries
        # fall back to file mtime
        self._mtimes: Dict[str, float] = {}
        self.stats = StoreStats()
        # concurrency: `_mem_lock` guards the memory tier; `_key_locks`
        # serializes writers/builders per key, so `get_or_build` races on
        # ONE key collapse to a single build (the rest become hits) while
        # disjoint keys proceed fully in parallel. Disk writes stay
        # atomic (tempfile + os.replace) regardless, so a reader racing a
        # writer sees either the old or the new complete pickle — never a
        # torn one (tests/test_artifacts_concurrent.py).
        self._mem_lock = threading.Lock()
        self._key_locks: Dict[str, threading.RLock] = {}

    def _key_lock(self, key: str) -> threading.RLock:
        with self._mem_lock:
            lock = self._key_locks.get(key)
            if lock is None:
                lock = self._key_locks[key] = threading.RLock()
            return lock

    # -- keys --------------------------------------------------------------

    @staticmethod
    def key(stage: str, spec: Any) -> str:
        """``<stage>-<hash(spec)>``: readable prefix, content-hashed body."""
        return f"{stage}-{stable_hash(spec)}"

    # -- low-level ---------------------------------------------------------

    def _path(self, key: str) -> Optional[Path]:
        return self.root / f"{key}.pkl" if self.root is not None else None

    def has(self, key: str) -> bool:
        with self._mem_lock:
            if key in self._memory:
                return True
        p = self._path(key)
        return p is not None and p.exists()

    def get(self, key: str) -> Any:
        with self._mem_lock:
            if key in self._memory:
                return self._memory[key]
        p = self._path(key)
        if p is not None and p.exists():
            # `os.replace` publishes pickles atomically, so this read sees
            # a complete file even mid-overwrite by a concurrent writer
            try:
                with open(p, "rb") as f:
                    obj = pickle.load(f)
            except Exception:
                # A corrupt/torn pickle (external damage — the store's own
                # writer is atomic) must not raise into the caller as if
                # the artifact existed: quarantine the file aside and
                # report a miss, so `get_or_build` rebuilds it.
                self._quarantine(key, p)
                raise KeyError(key) from None
            with self._mem_lock:
                # first load wins: every caller then shares one object
                obj = self._memory.setdefault(key, obj)
            return obj
        raise KeyError(key)

    def _quarantine(self, key: str, p: Path) -> None:
        """Rename a corrupt disk pickle to ``<key>.pkl.corrupt`` (numeric
        suffix if one is already parked) and count it in the stats."""
        q = Path(f"{p}.corrupt")
        i = 0
        while q.exists():
            i += 1
            q = Path(f"{p}.corrupt{i}")
        try:
            os.replace(p, q)
        except OSError:
            return            # concurrent reader already quarantined it
        self.stats.record_quarantine(key)

    def put(self, key: str, obj: Any, *, memory_only: bool = False) -> Any:
        import time
        with self._key_lock(key):
            with self._mem_lock:
                self._memory[key] = obj
                self._mtimes[key] = time.time()
            p = self._path(key)
            if p is not None and not memory_only:
                disk_obj = _to_numpy_tree(obj)
                # atomic write: a crashed run must not leave a torn pickle
                fd, tmp = tempfile.mkstemp(dir=str(self.root),
                                           prefix=f".{key}.")
                try:
                    with os.fdopen(fd, "wb") as f:
                        pickle.dump(disk_obj, f, protocol=4)
                    os.replace(tmp, p)
                except BaseException:
                    if os.path.exists(tmp):
                        os.unlink(tmp)
                    raise
        return obj

    def evict(self, key: str) -> None:
        with self._key_lock(key):
            with self._mem_lock:
                self._memory.pop(key, None)
                self._mtimes.pop(key, None)
            p = self._path(key)
            if p is not None and p.exists():
                p.unlink()

    def keys(self) -> Tuple[str, ...]:
        disk = ()
        if self.root is not None:
            disk = tuple(p.stem for p in self.root.glob("*.pkl"))
        with self._mem_lock:
            mem = set(self._memory)
        return tuple(sorted(mem | set(disk)))

    def gc_checkpoints(self, max_age_s: float,
                       prefix: str = "search_ckpt") -> Tuple[str, ...]:
        """Evict ``search_ckpt`` entries older than ``max_age_s`` seconds.

        A checkpointed search that finishes evicts its own checkpoint
        (`pipeline.stage_search`), so any checkpoint still in the store
        belongs to a run that is either in flight or dead. In-flight runs
        re-put the key every ``checkpoint_every`` generations, refreshing
        its timestamp; a key whose last write is older than ``max_age_s``
        is an orphan from a crashed/abandoned search and is swept here.
        Age comes from the store's own put timestamps (memory tier) or the
        pickle's file mtime (disk entries from a previous process).
        Called periodically by `repro.launch.serve.EvalService.health`;
        returns the evicted keys.
        """
        import time
        now = time.time()
        stale = []
        for key in self.keys():
            if not key.startswith(f"{prefix}-"):
                continue
            with self._mem_lock:
                ts = self._mtimes.get(key)
            if ts is None:
                p = self._path(key)
                try:
                    ts = p.stat().st_mtime if p is not None else None
                except OSError:
                    continue      # raced with an evict: already gone
            if ts is None or now - ts > max_age_s:
                self.evict(key)
                stale.append(key)
        return tuple(stale)

    # -- the stage entry point --------------------------------------------

    def get_or_build(self, stage: str, key: str, build: Callable[[], Any],
                     *, memory_only: bool = False) -> Any:
        """Return the cached artifact for ``key``, or build+cache it.

        ``memory_only`` keeps unpicklable artifacts (jitted engines) out of
        the disk tier while still memoizing them in-process.

        Concurrent-safe: callers racing on one key serialize on its key
        lock, so exactly one of them runs ``build()`` (recorded as the
        sole miss) and the rest are recorded as hits of the fresh
        artifact — hit + miss counts always sum to the number of calls.

        Fault-tolerant: a corrupt disk pickle surfaces from `get` as a
        `KeyError` (the file is quarantined as ``*.corrupt``), which this
        path treats as a plain miss and rebuilds — a damaged cache entry
        can cost a rebuild but never an exception or a wrong artifact."""
        with self._key_lock(key):
            try:
                obj = self.get(key)
            except KeyError:
                self.stats.record(stage, False, key)
                return self.put(key, build(), memory_only=memory_only)
            self.stats.record(stage, True, key)
            return obj
