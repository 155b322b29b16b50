"""Batched surrogate-evaluation engine for the DSE hot loop.

ApproxPilot's value proposition (PAPER.md Sec III-C) is that the GNN
surrogate makes evaluating millions of approximate-accelerator
configurations cheap enough to drive NSGA-III search. The samplers in
`repro.core.dse` only see an ``evaluate(configs) -> (n, n_obj)`` callable;
this module provides the production implementation of that callable:

``SurrogateEngine``
    Unifies the three evaluators — GNN surrogate (`from_gnn`), AutoAX
    random-forest baseline (`from_rforest`), synthesis oracle
    (`from_oracle`) — behind one batched interface with

    * **fixed-shape chunked inference** — batches are split into chunks of
      ``chunk_size`` and the ragged final chunk is padded up to the next
      power-of-two bucket, so the jit cache holds at most
      ``log2(chunk_size) + 1`` compiled shapes no matter how ragged the
      incoming batches are;
    * **multi-device dispatch** — with ``devices > 1`` the GNN paths
      place successive chunks on the host's devices in turn
      (`_over_devices`). A chunk is never split: each device runs the
      single-device program at the single-device shape, so every row is
      bit-identical to the ``devices=1`` engine
      (tests/test_engine_sharded.py);
    * **featurize/compute overlap** — the GNN backends are
      `PipelinedBackend`s (prepare → dispatch → collect); with ≥ 2 chunks
      a worker thread featurizes chunk *k+1* on the host (the schema-v2
      timing sweep; the functional probe is only dispatched, and its
      result is spliced into the features inside the forward's own
      program) while chunk *k* executes on device, and host transfers
      are deferred until every chunk is in flight — the LM
      decode-pipelining idiom. ``stats.overlap_fraction``
      reports the share of featurization the calling thread did not wait
      for;
    * **config-key memoization** — NSGA-II/III re-evaluations of surviving
      parents (and the stagnation-restart re-injections) are free across
      generations; duplicates inside a single batch are evaluated once;
    * **Pallas kernel dispatch** — on TPU the gcn/gsae GNN paths run
      their message-passing layers through the fused
      `repro.kernels.gnn_mp` kernel, held at construction to the pure-JAX
      forward by a parity check that raises on failure (there is no
      silent fallback; ``interpret=True`` off-TPU when forced);
    * **per-call stats** — configs/sec, cache hit rate, chunk/padding
      counts and per-phase timers (`EngineStats`), surfaced into
      ``PipelineResult.metrics``; each timer is the summed duration of a
      profiler span (`EngineStats.span`), so a trace names every phase.

Featurization is vectorized through the shared
`repro.core.dataset.ConfigFeaturizer`: every config of one accelerator
shares the graph topology, so adjacency, mask and all config-independent
feature columns are cached constants and the node-feature tensor is
assembled by table lookup (same cache as
`repro.core.dataset.features_for_configs`).

See docs/paper_map.md for how this maps onto the paper, and
benchmarks/engine_bench.py for the batched-vs-naive throughput numbers.
"""
from __future__ import annotations

import contextlib
import itertools
import queue as queue_lib
import threading
import time
import warnings
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, List, NamedTuple, Optional,
                    Sequence, Tuple)

import numpy as np

Config = Tuple[int, ...]
BatchFn = Callable[[Sequence[Config]], np.ndarray]

# fraction of a call's backend rows that may be ragged padding before the
# engine warns (once per engine): chronic padding at this level means the
# caller's batch sizes fight the power-of-two buckets and chunk_size
# should be retuned
PADDING_WARN_FRACTION = 0.25


# --------------------------------------------------------------------------
# stats
# --------------------------------------------------------------------------

@dataclass
class EngineStats:
    """Counters accumulated across `SurrogateEngine.__call__` invocations.

    Thread-safe: every mutation goes through `update` (or `bump_max`),
    which holds an internal lock, so counters stay exact when one engine
    serves many concurrent sessions (the serving daemon, the island
    orchestrator) — a bare ``stats.calls += 1`` from two threads can lose
    increments even under the GIL, because the read-modify-write is not
    atomic. `as_dict` snapshots all counters under the same lock.

    Attributes:
        calls:        number of ``engine(configs)`` invocations.
        configs:      total configs requested (including cache hits).
        cache_hits:   configs served from the memo cache (or deduped
                      within a batch).
        evaluated:    unique configs actually sent to the backend.
        padded:       wasted rows added to reach a fixed-shape bucket.
        chunks:       backend batch calls issued.
        max_batch:    largest single ``engine(configs)`` request seen —
                      the island fleet's fused per-generation block and
                      the serving daemon's cross-request drains show up
                      here.
        submits:      queries enqueued via `SurrogateEngine.submit` (the
                      cross-request batching path).
        drains:       `SurrogateEngine.drain` waves that evaluated at
                      least one submission; ``submits / drains`` is the
                      mean cross-request batch occupancy.
        retries:      backend calls re-issued by the engine's
                      `RetryPolicy` after a transient fault.
        quarantined:  configs whose objective rows stayed non-finite
                      after the nan-guard's re-evaluations; their rows
                      are served as +inf (never Pareto-optimal) and the
                      configs are recorded in ``engine.quarantined``.
        eval_time_s:  time inside the backend batch function.
        wall_time_s:  end-to-end time inside the engine (incl. cache
                      assembly).
        devices:      device count the backend shards chunks over (1 =
                      single-device; set at engine construction and
                      preserved across `reset_stats`).
        featurize_s:  host time in the pipelined backend's prepare stage
                      on the prefetch worker (featurization: table lookup
                      + dynamic timing sweep + functional probe).
        dispatch_s:   host time issuing device computation (non-blocking
                      under JAX async dispatch, so this is enqueue cost,
                      not compute).
        collect_s:    time blocked on device→host transfer + objective
                      post-processing (denorm, ssim flip). Device compute
                      not hidden by the pipeline surfaces here.
        feature_wait_s: time the calling thread waited for the prefetch
                      worker's features (pipelined calls only; it holds
                      the queue hand-offs too, so it can pass
                      ``featurize_s`` by those when nothing else keeps
                      the calling thread busy). ``overlap_fraction`` is
                      the share of ``featurize_s`` it did not wait for.
        timing_s:     the part of ``featurize_s`` in the timing sweep
                      (`batch_oracle.timing_batch`).
        probe_s:      the part of ``featurize_s`` in the functional
                      probe. On the GNN backends' path
                      (`ConfigFeaturizer.normalized_on_device`) that is
                      building the config block and dispatching the
                      probe, whose result stays on the device; where
                      features are made on the host
                      (`ConfigFeaturizer.normalized`), that dispatch and,
                      after the timing sweep, the wait to read it back.
        probe_on_device: chunks the prefetch worker featurized whose
                      probe result stayed on the device, spliced into the
                      forward in its own program (equals ``chunks`` on a
                      pipelined GNN call; 0 where the probe is read back
                      on the host or there is none).
        memo_s:       memo key building and lookup, plus cache insertion,
                      eviction and row assembly.
        guard_s:      the part of ``collect_s`` reading the functional
                      probe's LUT-domain guards (the pipelined GNN
                      backends' ``check`` phase; 0 where the probe reads
                      no truth table).
        lut_reads:    truth-table entries the functional probes gathered
                      on the device (LUT applications per pixel x probe
                      pixels x configurations, counted when the labeler
                      is traced, never on the device; 0 without LUT
                      units).
        lut_square_reads: of ``lut_reads``, the entries squarers read
                      from their tables' diagonals (0 where no LUT unit
                      is fed one operand twice).

    ``wall_time_s`` and the timers from ``featurize_s`` on are summed
    durations of the engine's `span`s (``engine.*`` on the calling
    thread, ``featurize.*`` where features are made), so a profiler
    trace and the counters measure the same intervals.
    """
    calls: int = 0
    configs: int = 0
    cache_hits: int = 0
    evaluated: int = 0
    padded: int = 0
    chunks: int = 0
    max_batch: int = 0
    submits: int = 0
    drains: int = 0
    retries: int = 0
    quarantined: int = 0
    eval_time_s: float = 0.0
    wall_time_s: float = 0.0
    devices: int = 1
    featurize_s: float = 0.0
    dispatch_s: float = 0.0
    collect_s: float = 0.0
    feature_wait_s: float = 0.0
    timing_s: float = 0.0
    probe_s: float = 0.0
    memo_s: float = 0.0
    probe_on_device: int = 0
    guard_s: float = 0.0
    lut_reads: int = 0
    lut_square_reads: int = 0

    def __post_init__(self):
        self._lock = threading.Lock()

    def update(self, **deltas) -> None:
        """Atomically add `deltas` to the named counters."""
        with self._lock:
            for name, d in deltas.items():
                setattr(self, name, getattr(self, name) + d)

    @contextlib.contextmanager
    def span(self, name: str, counter: Optional[str] = None, **args):
        """A profiler span (`jax.profiler.TraceAnnotation` with `args`)
        whose duration is also added to the timer `counter`, so the span
        and the counter share one start and one end. Yields the
        annotation (``set_metadata`` adds args known only later). Costs
        about a microsecond when no profiler is attached."""
        from jax.profiler import TraceAnnotation

        with TraceAnnotation(name, **args) as ann:
            t0 = time.perf_counter()
            try:
                yield ann
            finally:
                if counter is not None:
                    self.update(**{counter: time.perf_counter() - t0})

    def bump_max(self, **candidates) -> None:
        """Atomically raise the named high-water-mark counters."""
        with self._lock:
            for name, v in candidates.items():
                if v > getattr(self, name):
                    setattr(self, name, v)

    @property
    def cache_hit_rate(self) -> float:
        return self.cache_hits / self.configs if self.configs else 0.0

    @property
    def configs_per_sec(self) -> float:
        return self.configs / self.wall_time_s if self.wall_time_s else 0.0

    @property
    def batch_occupancy(self) -> float:
        """Mean submissions coalesced per drain wave (1.0 = no batching
        benefit; > 1 means cross-request batching is happening)."""
        return self.submits / self.drains if self.drains else 0.0

    @property
    def padded_fraction(self) -> float:
        """Share of backend rows that were ragged-chunk padding waste."""
        total = self.evaluated + self.padded
        return self.padded / total if total else 0.0

    @property
    def overlap_fraction(self) -> float:
        """Share of host featurization the calling thread did not wait
        for, ``1 - feature_wait_s / featurize_s`` (0.0 with no
        featurization; near 0 when the device outruns the featurizer)."""
        return 1.0 - self.feature_wait_s / self.featurize_s \
            if self.featurize_s else 0.0

    def as_dict(self) -> Dict[str, float]:
        with self._lock:
            snap = {"calls": self.calls, "configs": self.configs,
                    "cache_hits": self.cache_hits,
                    "evaluated": self.evaluated,
                    "padded": self.padded, "chunks": self.chunks,
                    "max_batch": self.max_batch,
                    "submits": self.submits, "drains": self.drains,
                    "retries": self.retries,
                    "quarantined": self.quarantined,
                    "eval_time_s": round(self.eval_time_s, 4),
                    "wall_time_s": round(self.wall_time_s, 4),
                    "devices": self.devices,
                    "featurize_s": round(self.featurize_s, 4),
                    "dispatch_s": round(self.dispatch_s, 4),
                    "collect_s": round(self.collect_s, 4),
                    "feature_wait_s": round(self.feature_wait_s, 4),
                    "timing_s": round(self.timing_s, 4),
                    "probe_s": round(self.probe_s, 4),
                    "memo_s": round(self.memo_s, 4),
                    "probe_on_device": self.probe_on_device,
                    "guard_s": round(self.guard_s, 4),
                    "lut_reads": self.lut_reads,
                    "lut_square_reads": self.lut_square_reads}
            overlap = self.overlap_fraction
        snap["cache_hit_rate"] = round(
            snap["cache_hits"] / snap["configs"], 4) if snap["configs"] \
            else 0.0
        snap["configs_per_sec"] = round(
            snap["configs"] / snap["wall_time_s"], 1) \
            if snap["wall_time_s"] else 0.0
        snap["batch_occupancy"] = round(
            snap["submits"] / snap["drains"], 3) if snap["drains"] else 0.0
        total = snap["evaluated"] + snap["padded"]
        snap["padded_fraction"] = round(snap["padded"] / total, 4) \
            if total else 0.0
        snap["overlap_fraction"] = round(overlap, 4)
        return snap


# --------------------------------------------------------------------------
# vectorized featurization (GNN / RF paths)
# --------------------------------------------------------------------------

class _ConfigFeaturizer:
    """Config -> normalized node-feature tensor, by table lookup.

    Thin engine-facing wrapper over the shared
    `repro.core.dataset.ConfigFeaturizer` (cached via
    `dataset.featurizer_for`, so the engine and `features_for_configs`
    reuse one set of precomputed constant columns). Produces tensors
    bit-identical to `repro.core.dataset.features_for_configs` (asserted
    in tests/test_engine.py).
    """

    def __init__(self, ds, app, entries: Dict[str, Sequence]):
        from repro.core import dataset as ds_lib

        feat = ds_lib.featurizer_for(ds, app, entries)
        self._feat = feat
        self.schema = feat.schema
        self.n_pad = feat.n_pad
        self.sizes = feat.sizes
        self.adj = feat.adj                                # (N, N) normalized
        self.mask = feat.mask                              # (N,)
        self.n_nodes = feat.n_nodes
        self.probe_columns = feat.probe_columns
        self.probe_guards = feat.probe_guards

    def __call__(self, configs: Sequence[Config],
                 stats: Optional[EngineStats] = None) -> np.ndarray:
        return self._feat.normalized(configs, stats)

    def on_device(self, configs: Sequence[Config],
                  stats: Optional[EngineStats] = None):
        """``(X, probe)``: host features with the functional probe left on
        the device (`_featurize_on_device`)."""
        return _featurize_on_device(self._feat, configs, stats)


def _featurize_on_device(feat, configs: Sequence[Config],
                         stats: Optional[EngineStats] = None):
    """``feat.normalized_on_device(configs, stats)``, counting the chunk
    in ``stats.probe_on_device`` when its probe stayed on the device."""
    X, probe = feat.normalized_on_device(configs, stats)
    if stats is not None and probe.ssim:
        stats.update(probe_on_device=1)
    return X, probe


# --------------------------------------------------------------------------
# pipelined backends: prepare (host) -> dispatch (device) -> collect (host)
# --------------------------------------------------------------------------

class PipelinedBackend:
    """A batch backend split into its host and device phases.

    The composed call ``collect(dispatch(prepare(configs)))`` is the plain
    ``batch_fn`` contract, so a `PipelinedBackend` drops into every
    existing engine path (retry, nan-guard heal, naive comparisons). The
    split exists so `SurrogateEngine._eval_chunked` can overlap the
    phases across chunks:

    * ``prepare(configs, stats=None) -> X`` — host-side featurization
      (NumPy table lookup plus, under schema v2, the batched timing sweep
      and the tiny-image functional probe; the GNN constructors leave the
      probe's result on the device, so their ``X`` is the host features
      with a `batch_oracle.DeviceProbe`). Runs on the prefetch worker
      thread. The engine passes its `EngineStats`, into whose
      ``timing_s``/``probe_s`` the featurizer counts its parts.
    * ``dispatch(X) -> handle`` — hand the features to the device and
      start compute. Under JAX async dispatch the jitted call returns
      immediately with a future-like device array, so the engine can keep
      dispatching while earlier chunks execute. With ``devices > 1`` the
      GNN constructors put each chunk on the next device here.
    * ``check(handle)`` — optional: raise if the dispatched chunk's
      result cannot be trusted. The GNN constructors read the functional
      probe's LUT-domain guards here (`_guard_check`); the engine runs it
      first in ``collect``'s span, in an ``engine.guards`` span of its
      own.
    * ``collect(handle) -> (B, n_obj) ndarray`` — block on the device
      result, transfer, and post-process (denormalize, ssim flip).

    ``devices`` records, for `EngineStats`, how many devices the chunks
    are spread over.
    """

    def __init__(self, prepare: Callable[[Sequence[Config]], Any],
                 dispatch: Callable[[Any], Any],
                 collect: Callable[[Any], np.ndarray], *,
                 check: Optional[Callable[[Any], None]] = None,
                 devices: int = 1):
        self.prepare = prepare
        self.dispatch = dispatch
        self.check = check
        self.collect = collect
        self.devices = max(1, int(devices))

    def __call__(self, configs: Sequence[Config]) -> np.ndarray:
        handle = self.dispatch(self.prepare(configs))
        if self.check is not None:
            self.check(handle)
        return self.collect(handle)


def _resolve_devices(devices) -> int:
    """Normalize the ``devices`` knob to a shard cap.

    ``1``/``None`` = single-device (no sharding, no mesh work at all);
    ``0`` or ``"auto"`` = every local device; ``N > 1`` = at most N local
    devices. Resolution imports jax lazily so plain-NumPy engines never
    pull it in."""
    if devices is None or devices == 1:
        return 1
    if devices == 0 or devices == "auto":
        import jax
        return len(jax.devices())
    n = int(devices)
    if n < 0:
        raise ValueError(f"devices must be >= 0 or 'auto', got {devices}")
    import jax
    return max(1, min(n, len(jax.devices())))


def _over_devices(fn, n_devices: int):
    """``fn`` with successive calls placed on up to `n_devices` local
    devices in turn; ``fn`` itself when the cap is 1 (single-device
    engines never touch device placement).

    The call's argument may be a pytree (the GNN backends pass the host
    features with the device probe's SSIM vectors); all of it moves to
    the device. A call's rows stay together on one device, which runs the
    single-device program at the single-device shape. Splitting a chunk
    instead would change its rows: their last bits depend on the program
    XLA compiles around them (on a TPU the pure-JAX forward's node-axis
    sums give different bits in a 128-row and a 512-row call, and a
    sharded program differs again). The pipelined engine dispatches every
    chunk of a wave before collecting any, so a multi-chunk wave keeps
    all the devices busy."""
    if n_devices <= 1:
        return fn
    import jax

    devices = itertools.cycle(jax.devices()[:n_devices])
    lock = threading.Lock()

    def call(X):
        with lock:
            device = next(devices)
        return fn(jax.device_put(X, device))
    return call


# --------------------------------------------------------------------------
# GNN predict functions (pure-JAX and Pallas-kernel paths)
# --------------------------------------------------------------------------
#
# Both forwards run their contractions at ``Precision.HIGHEST`` (float32 on
# the TPU MXU; XLA's default there is one bfloat16 pass). They then differ
# only in summation order, which over the paper's widest surrogate (hidden
# 300, 5 layers per stage) moves a normalized output by far less than
# KERNEL_PARITY_TOL; a bfloat16 gap would not.

KERNEL_PARITY_TOL = 1e-4     # rtol and atol, on normalized targets


def _no_splice(X):
    return X


def _probe_splice(feat) -> Callable:
    """The first step of every GNN backend's forward, traced into its
    program: ``(X, ssim) -> X`` with each device probe scale's
    standardized distortion ``((1 - s) - mean) / std`` written into its
    probe column of the real graph rows (``feat.probe_columns()``), the
    float32 expression `ConfigFeaturizer.normalized` applies on the host;
    padding rows keep `normalized`'s values. A forward made without it
    (`_no_splice`) takes plain features."""
    import jax

    cols, n = feat.probe_columns(), feat.n_nodes

    def splice(inputs):
        X, ssim = inputs
        for (col, mu, sd), s in zip(cols, ssim):
            # the barrier hides the constants from XLA's simplifier, which
            # would otherwise fold them into (1 - mu) - s and a multiply
            # by 1/sd: far from the host's bits where the column is near 0
            mu, sd = jax.lax.optimization_barrier((mu, sd))
            X = X.at[:, :n, col].set((((1 - s) - mu) / sd)[:, None])
        return X

    return splice


class _Dispatched(NamedTuple):
    """A GNN backend's dispatched chunk: the forward's device output
    (``out``) and the probe's LUT-guard ``check``, which the backend's
    ``check`` phase runs before ``collect`` reads the rows."""
    out: Any
    check: Callable[[], None]

    def devices(self):
        """The devices holding the output (`jax.Array.devices`)."""
        import jax
        return set().union(*(a.devices() for a in jax.tree.leaves(self.out)))


def _guard_check(feat) -> Optional[Callable[[_Dispatched], None]]:
    """The GNN backends' ``check`` phase: the functional probe's LUT
    guards of a dispatched chunk, or None where the probe reads no truth
    table (``feat.probe_guards``), so there is nothing to read."""
    if not feat.probe_guards:
        return None
    return lambda handle: handle.check()


def _make_jax_predict(two_cfg, params, adj_row: np.ndarray,
                      mask_row: np.ndarray, splice: Callable = _no_splice):
    """jit'd X -> normalized (B, 4) targets via `models.predict`; ``X`` is
    first passed through ``splice`` (`_probe_splice`)."""
    import jax
    import jax.numpy as jnp
    from repro.core import models

    A = jnp.asarray(adj_row)
    m = jnp.asarray(mask_row)

    @jax.jit
    def f(X):
        X = splice(X)
        B = X.shape[0]
        adj = jnp.broadcast_to(A, (B,) + A.shape)
        mask = jnp.broadcast_to(m, (B,) + m.shape)
        with jax.default_matmul_precision("highest"):
            return models.predict(two_cfg, params, adj, X, mask)[0]

    return f


def _make_kernel_predict(two_cfg, params, adj_row: np.ndarray,
                         mask_row: np.ndarray, graph_block: int = 8,
                         splice: Callable = _no_splice):
    """jit'd X -> normalized (B, 4), message passing via Pallas `gnn_mp`;
    ``X`` is first passed through ``splice`` (`_probe_splice`).

    Supports the gcn and gsae architectures, whose layer update is exactly
    the kernel's fused ``relu(A' @ (H @ Wn) + H @ Ws + b)`` with
    ``A' = adj`` (gcn) or ``A' = adj / deg`` (GraphSAGE-mean: row-scaling
    the adjacency commutes with the matmul). Readout and the two-stage
    critical-path bit injection replicate `gnn.apply` / `models.predict`.
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    crit_idx = two_cfg.schema.crit_index

    def scaled_adj(cfg):
        a = np.asarray(adj_row, np.float32)
        if cfg.arch == "gsae":
            deg = np.maximum(a.sum(-1, keepdims=True), 1e-6)
            a = a / deg
        return jnp.asarray(a)

    def stack(cfg, p, adj_k, x, mask):
        h = x * mask[..., None]
        for lp in p["layers"]:
            h = ops.gnn_mp(adj_k, h, lp["w_self"], lp["w_nbr"], lp["b"],
                           graph_block=graph_block)
            h = h * mask[..., None]
        return h

    def readout(cfg, p, h, mask):
        if cfg.node_level:
            out = jax.nn.relu(h @ p["ro_w1"] + p["ro_b1"])
            return out @ p["ro_w2"] + p["ro_b2"]
        denom = jnp.maximum(mask.sum(-1, keepdims=True), 1.0)
        mean = (h * mask[..., None]).sum(1) / denom
        mx = jnp.where(mask[..., None] > 0, h, -1e30).max(1)
        g = jnp.concatenate([mean, mx], -1)
        g = jax.nn.relu(g @ p["ro_w1"] + p["ro_b1"])
        return g @ p["ro_w2"] + p["ro_b2"]

    s1, s2 = two_cfg.stage1, two_cfg.stage2
    if s1.arch not in ("gcn", "gsae"):
        raise ValueError(f"kernel path supports gcn/gsae, not {s1.arch}")
    A1 = scaled_adj(s1)
    m_row = jnp.asarray(mask_row)

    def forward(X):
        B = X.shape[0]
        adj_k = jnp.broadcast_to(A1, (B,) + A1.shape)
        mask = jnp.broadcast_to(m_row, (B,) + m_row.shape)
        h1 = stack(s1, params.stage1, adj_k, X, mask)
        crit_logits = readout(s1, params.stage1, h1, mask)[..., 0]
        if two_cfg.use_critical_path:
            bit = (jax.nn.sigmoid(crit_logits) > 0.5).astype(X.dtype)
        else:
            bit = jnp.zeros_like(crit_logits)
        x2 = X.at[..., crit_idx].set(bit * mask)
        h2 = stack(s2, params.stage2, adj_k, x2, mask)
        return readout(s2, params.stage2, h2, mask)

    @jax.jit
    def f(X):
        with jax.default_matmul_precision("highest"):
            return forward(splice(X))

    return f


# --------------------------------------------------------------------------
# the engine
# --------------------------------------------------------------------------

class SurrogateEngine:
    """Batched, memoized evaluator: ``engine(configs) -> (n, n_obj)``.

    Drop-in `repro.core.dse.EvalFn`: samplers call it exactly like a plain
    function. Construct via `from_gnn` / `from_rforest` / `from_oracle`
    for the three ApproxPilot evaluators, or wrap any batch callable
    directly (used by `repro.core.lm_bridge` and the DSE samplers'
    `dse.as_engine`).

    Args:
        batch_fn:    ``configs -> (len(configs), n_obj)`` backend, or a
                     `PipelinedBackend` whose prepare/dispatch/collect
                     phases the engine overlaps across chunks.
        backend:     label for stats/reporting ("jax", "pallas", ...).
        chunk_size:  maximum configs per backend call. ``None`` disables
                     chunking entirely — the whole miss list goes to the
                     backend in one call (used by `queued_view`, whose
                     coalescing decisions belong to the drain side; only
                     valid with ``fixed_shape=False``).
        overlap:     pipeline chunk evaluation when the backend is a
                     `PipelinedBackend` and a call spans >= 2 chunks:
                     chunk k+1 featurizes on a worker thread while chunk
                     k computes on device, and transfers are deferred
                     until every chunk is in flight. Bit-identical to the
                     serial path (the identical phase functions run in
                     the identical per-chunk order). ``None`` = auto (on
                     exactly when the backend is pipelined); ``False``
                     forces the serial path.
        fixed_shape: pad ragged final chunks up to a power-of-two bucket so
                     jit-compiled backends see a bounded set of shapes.
                     Leave False for shape-insensitive backends (oracle,
                     numpy random forest).
        cache:       memoize results by config key across calls. Assumes a
                     deterministic backend (true for all evaluators here);
                     disable for stochastic evaluators.
        max_cache:   cache entry bound; oldest entries evicted beyond it.
        obj_cols:    when the backend returns extra per-config columns
                     beyond the objectives (the ensemble backend appends a
                     per-objective std), the first `obj_cols` columns are
                     the objectives served by ``__call__`` and the rest is
                     the uncertainty block served by ``uncertainty`` /
                     ``predict_with_uncertainty``. None = all columns are
                     objectives (no uncertainty available).
        retry:       `repro.distributed.fault.RetryPolicy` applied around
                     every backend call: transient faults (HostFailure /
                     StragglerStall — anything `TransientError`) are
                     re-issued with bounded exponential backoff and
                     counted in ``stats.retries``. None = no retry
                     (backend exceptions propagate on first raise).
        nan_guard:   guard every backend result against non-finite
                     objective rows: offending configs are re-evaluated
                     individually (``nan_retries`` extra attempts each —
                     heals one-shot corruption like an injected NaN wave
                     bit-identically); configs whose rows STAY non-finite
                     are quarantined — their row is served as +inf (a
                     dominated point that can never poison a Pareto
                     front), the config key lands in
                     ``engine.quarantined``, and ``stats.quarantined``
                     counts them. On by default: a single NaN row from a
                     flaky backend must not invalidate a 10^5-config
                     search.
    """

    def __init__(self, batch_fn: BatchFn, *, backend: str = "generic",
                 chunk_size: Optional[int] = 512,
                 fixed_shape: bool = False,
                 overlap: Optional[bool] = None,
                 cache: bool = True, max_cache: int = 1_000_000,
                 obj_cols: Optional[int] = None, retry=None,
                 nan_guard: bool = True, nan_retries: int = 2,
                 schema_version: Optional[int] = None):
        if chunk_size is not None and chunk_size < 1:
            raise ValueError("chunk_size must be >= 1 (or None to "
                             "disable chunking)")
        if chunk_size is None and fixed_shape:
            raise ValueError("fixed_shape needs chunking: power-of-two "
                             "buckets are capped at chunk_size")
        self._batch_fn = batch_fn
        self._pipeline = batch_fn if isinstance(batch_fn, PipelinedBackend) \
            else None
        self.overlap = (self._pipeline is not None) if overlap is None \
            else bool(overlap)
        self.devices = self._pipeline.devices if self._pipeline else 1
        self._warned_padding = False
        self.backend = backend
        # feature-schema version of the backend's featurization, when it
        # has one (the GNN/RF paths): memo keys are prefixed with it so a
        # cache shared or persisted across schema bumps can never serve a
        # stale-layout row to a new-schema model
        self.schema_version = schema_version
        self.chunk_size = None if chunk_size is None else int(chunk_size)
        self.fixed_shape = fixed_shape
        self.cache_enabled = cache
        self.max_cache = max_cache
        self.obj_cols = obj_cols
        self.retry = retry
        self.nan_guard = nan_guard
        self.nan_retries = int(nan_retries)
        self.quarantined: set = set()
        self._cache: Dict[Config, np.ndarray] = {}
        self.stats = EngineStats(devices=self.devices)
        # one engine may serve several concurrent samplers (the island
        # orchestrator, repro.core.islands); the lock keeps cache/stats
        # mutation and backend dispatch coherent under that sharing
        self._lock = threading.RLock()
        # cross-request batching queue (see submit/drain): pending
        # (configs, future) submissions plus a condition variable the
        # serving daemon's batcher thread blocks on
        self._queue: List[Tuple[List[Config], "Future"]] = []
        self._queue_cv = threading.Condition()

    # -- public API --------------------------------------------------------

    def __call__(self, configs: Sequence[Config]) -> np.ndarray:
        """Evaluate a batch of configs; rows align with the input order.

        Thread-safe: concurrent callers are serialized on an internal
        lock (results are deterministic regardless of arrival order)."""
        with self._lock:
            out = self._call_locked(configs)
        return out[:, :self.obj_cols] if self.obj_cols else out

    def uncertainty(self, configs: Sequence[Config]) -> np.ndarray:
        """Per-config, per-objective uncertainty (ensemble std) rows.

        Served from the same memoized rows as ``__call__`` — the DSE
        acquisition path can ask for the std of configs it just evaluated
        at zero extra backend cost. Raises unless the engine was built
        with an uncertainty-producing backend (`from_gnn_ensemble`)."""
        if not self.obj_cols:
            raise ValueError(
                f"engine backend {self.backend!r} does not produce an "
                f"uncertainty column (build it with from_gnn_ensemble)")
        with self._lock:
            out = self._call_locked(configs)
        return out[:, self.obj_cols:]

    def predict_with_uncertainty(self, configs: Sequence[Config]
                                 ) -> Tuple[np.ndarray, np.ndarray]:
        """(objectives (n, obj_cols), std (n, obj_cols)) in one pass."""
        if not self.obj_cols:
            raise ValueError(
                f"engine backend {self.backend!r} does not produce an "
                f"uncertainty column (build it with from_gnn_ensemble)")
        with self._lock:
            out = self._call_locked(configs)
        return out[:, :self.obj_cols], out[:, self.obj_cols:]

    def _call_locked(self, configs: Sequence[Config]) -> np.ndarray:
        st = self.stats
        st.update(calls=1, configs=len(configs))
        st.bump_max(max_batch=len(configs))
        call = st.calls
        with st.span("engine.call", "wall_time_s", call=call,
                     configs=len(configs)) as call_span:
            with st.span("engine.memo", "memo_s", call=call):
                raw = [tuple(int(v) for v in c) for c in configs]
                sv = self.schema_version
                keys = raw if sv is None else [(sv,) + k for k in raw]
                miss: List[Config] = []       # raw configs for the backend
                miss_keys: List[Config] = []  # their (prefixed) memo keys
                seen = set()
                for k, r in zip(keys, raw):
                    if k not in self._cache and k not in seen:
                        seen.add(k)
                        miss.append(r)
                        miss_keys.append(k)
            call_span.set_metadata(misses=len(miss))
            st.update(cache_hits=len(keys) - len(miss))
            rows = []
            if miss:
                t0 = time.perf_counter()
                rows = self._eval_chunked(miss, call)
                st.update(eval_time_s=time.perf_counter() - t0,
                          evaluated=len(miss))
            with st.span("engine.assemble", "memo_s", call=call):
                for k, r in zip(miss_keys, rows):
                    self._cache[k] = r
                out = np.stack([self._cache[k] for k in keys],
                               0).astype(np.float64)
                if not self.cache_enabled:
                    self._cache.clear()
                elif len(self._cache) > self.max_cache:
                    drop = len(self._cache) - self.max_cache
                    for k in list(itertools.islice(self._cache, drop)):
                        del self._cache[k]
        return out

    def reset_stats(self) -> None:
        """Zero the counters (cache contents and the engine's device
        width are kept)."""
        with self._lock:
            self.stats = EngineStats(devices=self.devices)

    def clear_cache(self) -> None:
        """Drop all memoized results."""
        with self._lock:
            self._cache.clear()

    @property
    def cache_size(self) -> int:
        return len(self._cache)

    # -- cross-request batching queue --------------------------------------
    #
    # The serving daemon (repro.launch.serve.EvalService) routes every
    # in-flight request's surrogate queries through submit(); ONE batcher
    # thread repeatedly drain()s, so queries that arrive while the backend
    # is busy coalesce into the next fused evaluation — the LM-server
    # decode-batching idiom applied to surrogate inference. Results are
    # bit-identical to direct ``engine(configs)`` calls: drain() feeds the
    # union through the same memoized/chunked ``__call__`` path and slices
    # each submission's rows back out by position.

    def submit(self, configs: Sequence[Config]) -> "Future":
        """Enqueue a query; the returned future resolves to the same
        ``(len(configs), n_obj)`` rows a direct call would produce once a
        drain wave (any thread calling `drain`) picks it up."""
        from concurrent.futures import Future

        fut: Future = Future()
        cfgs = list(configs)
        if not cfgs:
            fut.set_result(np.zeros((0, self.obj_cols or 0), np.float64))
            return fut
        with self._queue_cv:
            self._queue.append((cfgs, fut))
            self.stats.update(submits=1)
            self._queue_cv.notify_all()
        return fut

    def pending(self) -> int:
        """Number of submissions waiting for a drain wave."""
        with self._queue_cv:
            return len(self._queue)

    def drain(self, timeout: Optional[float] = None) -> int:
        """Evaluate ALL pending submissions as one fused engine call.

        Blocks up to `timeout` seconds for a first submission to arrive
        (``None`` = don't wait), then takes the whole queue — everything
        that piled up while the previous wave was evaluating — runs the
        concatenated configs through ``__call__`` (memo dedupe + fixed
        chunking), and resolves each future with its slice. Returns the
        number of submissions served; their count is the cross-request
        batch occupancy tracked by ``stats.submits / stats.drains``.

        Never raises on backend failure: if the fused wave throws, each
        submission is re-evaluated on its own so only the offending
        submissions' futures carry the exception — innocent requests
        coalesced into the same wave still get their rows, and the
        calling batcher thread stays alive.
        """
        with self._queue_cv:
            if not self._queue and timeout is not None:
                self._queue_cv.wait(timeout)
            batch, self._queue = self._queue, []
        if not batch:
            return 0
        flat: List[Config] = []
        for cfgs, _ in batch:
            flat.extend(cfgs)
        try:
            rows = self(flat)
        except BaseException:      # noqa: BLE001 — isolate the bad apple
            # Wave-failure isolation: a single bad submission (e.g. an
            # out-of-range config) must not fail everything coalesced
            # into this wave. Serve each submission individually; every
            # future gets its own rows or its own exception.
            for cfgs, fut in batch:
                try:
                    fut.set_result(self(cfgs))
                except BaseException as e:  # noqa: BLE001 — to caller
                    fut.set_exception(e)
            self.stats.update(drains=1)
            return len(batch)
        self.stats.update(drains=1)
        off = 0
        for cfgs, fut in batch:
            fut.set_result(rows[off:off + len(cfgs)])
            off += len(cfgs)
        return len(batch)

    def abort_pending(self, exc: Optional[BaseException] = None) -> int:
        """Fail all queued submissions (service shutdown); returns count."""
        with self._queue_cv:
            batch, self._queue = self._queue, []
        exc = exc or RuntimeError("engine queue aborted")
        for _, fut in batch:
            fut.set_exception(exc)
        return len(batch)

    def queued_view(self, *, cache: bool = True,
                    timeout: Optional[float] = 120.0) -> "SurrogateEngine":
        """A per-request engine facade that routes through the queue.

        Looks exactly like an engine to the DSE samplers (``as_engine``
        passes it through untouched), but its backend is
        ``submit(...).result()`` against *this* shared engine — so every
        caller holding a view participates in cross-request batching
        while keeping private stats (`DSEResult.stats` then reports the
        request's own traffic). The view does no chunking or padding of
        its own — ``chunk_size=None`` is the engine's explicit
        no-chunking mode, so one sampler query is one submission and all
        coalescing decisions stay with the drain side — and memoizes
        locally on top of the shared memo. Views serve objective rows
        only (the shared ``__call__`` slices off any uncertainty block
        before the rows reach the queue).
        """
        parent = self

        def batch_fn(configs: Sequence[Config]) -> np.ndarray:
            return parent.submit(configs).result(timeout=timeout)

        return SurrogateEngine(batch_fn, backend=f"queued:{self.backend}",
                               chunk_size=None, fixed_shape=False,
                               cache=cache)

    # -- chunking ----------------------------------------------------------

    def _bucket(self, n: int) -> int:
        """Smallest power-of-two >= n, capped at chunk_size."""
        b = 1
        while b < n:
            b <<= 1
        return min(b, self.chunk_size)

    def _eval_backend(self, chunk: List[Config]) -> np.ndarray:
        """One backend call, re-issued under `self.retry` on transient
        faults (`stats.retries` counts every re-issue)."""
        if self.retry is None:
            return np.asarray(self._batch_fn(chunk))
        return np.asarray(self.retry.call(
            self._batch_fn, chunk,
            on_retry=lambda e: self.stats.update(retries=1)))

    def _guard_rows(self, part: List[Config], y: np.ndarray) -> np.ndarray:
        """Non-finite-row guard: heal corrupted rows by re-evaluating the
        offending configs individually; quarantine persistent offenders.

        One-shot corruption (an injected NaN wave, a transient numeric
        fault) heals bit-identically because the re-evaluation hits the
        same deterministic backend. A config whose row is non-finite on
        every attempt is quarantined: its row becomes +inf (strictly
        dominated, so it can never contaminate a Pareto front), its key
        joins ``self.quarantined`` and ``stats.quarantined`` counts it.
        """
        bad = np.where(~np.all(np.isfinite(y), axis=1))[0]
        if not len(bad):
            return y
        y = np.array(y, copy=True)
        for j in bad:
            healed = False
            for _ in range(self.nan_retries):
                row = self._eval_backend([part[j]])[0]
                if np.all(np.isfinite(row)):
                    y[j] = row
                    healed = True
                    break
            if not healed:
                y[j] = np.inf
                self.quarantined.add(part[j])
                self.stats.update(quarantined=1)
        return y

    def _plan_chunks(self, configs: List[Config]
                     ) -> List[Tuple[int, int, List[Config]]]:
        """Split the miss list into ``(start, take, padded_chunk)`` work
        items. ``chunk_size=None`` plans the whole list as one chunk (the
        explicit no-chunking mode `queued_view` uses); fixed-shape
        padding up to the power-of-two bucket is applied and counted
        here."""
        plan: List[Tuple[int, int, List[Config]]] = []
        i, n = 0, len(configs)
        size = n if self.chunk_size is None else self.chunk_size
        while i < n:
            take = min(size, n - i)
            chunk = configs[i:i + take]
            if self.fixed_shape and take < self.chunk_size:
                b = self._bucket(take)
                self.stats.update(padded=b - take)
                chunk = chunk + [chunk[-1]] * (b - take)
            plan.append((i, take, chunk))
            i += take
        return plan

    def _warn_padding(self, plan, n_configs: int) -> None:
        """One-line, once-per-engine warning when ragged padding exceeds
        `PADDING_WARN_FRACTION` of a wave's backend rows — chronic waste
        at this level means the caller's batch shapes fight the
        power-of-two buckets and ``chunk_size`` should be retuned."""
        if self._warned_padding:
            return
        pad_rows = sum(len(c) - take for _, take, c in plan)
        total = pad_rows + n_configs
        if pad_rows and pad_rows > PADDING_WARN_FRACTION * total:
            self._warned_padding = True
            warnings.warn(
                f"engine[{self.backend}]: {pad_rows}/{total} backend rows "
                f"({pad_rows / total:.0%}) in this wave are ragged-chunk "
                f"padding (> {PADDING_WARN_FRACTION:.0%} of the wave) — "
                f"retune chunk_size or the caller's batch shape "
                f"(stats.padded_fraction tracks the running rate)",
                RuntimeWarning, stacklevel=4)

    def _eval_chunked(self, configs: List[Config],
                      call: int) -> np.ndarray:
        plan = self._plan_chunks(configs)
        self._warn_padding(plan, len(configs))
        if self.overlap and self._pipeline is not None and len(plan) >= 2:
            return self._eval_pipelined(plan, configs, call)
        rows = []
        for idx, (i, take, chunk) in enumerate(plan):
            # the composed call, retries included, in one span: its phases
            # are not split here, so no phase timer counts them (the
            # featurizer's own spans still open inside it)
            with self.stats.span("engine.backend", call=call, chunk=idx):
                y = self._eval_backend(chunk)
            if y.shape[0] != len(chunk):
                raise ValueError(
                    f"backend returned {y.shape[0]} rows for "
                    f"{len(chunk)} configs")
            part = y[:take]
            if self.nan_guard and not np.all(np.isfinite(part)):
                part = self._guard_rows(configs[i:i + take], part)
            rows.append(part)
            self.stats.update(chunks=1)
        return np.concatenate(rows, 0)

    def _eval_pipelined(self, plan: List[Tuple[int, int, List[Config]]],
                        configs: List[Config], call: int) -> np.ndarray:
        """Two-stage pipelined execution of the chunk plan (the LM decode
        idiom): ONE worker thread runs the backend's host ``prepare``
        (featurization: table lookup + timing sweep + functional probe)
        into a bounded two-slot queue while the main thread ``dispatch``es
        chunks to the device — non-blocking under JAX async dispatch — so
        chunk k+1 featurizes while chunk k computes; the blocking
        ``collect`` (device→host transfer + post-processing) is deferred
        until every chunk is in flight.

        Bit-identical to the serial path: the identical three phase
        functions run once per (identically padded) chunk in the identical
        order — only wall-clock interleaving changes. Any chunk whose
        phase raises is re-evaluated through `_eval_backend` (the composed
        call, under the engine's RetryPolicy), preserving the serial
        path's retry/nan-guard fault semantics.

        Spans: ``featurize.chunk`` on the worker; ``engine.wait_features``,
        ``engine.dispatch`` and ``engine.collect`` on the calling thread,
        each with the engine call number and the plan index; inside
        ``engine.collect``, ``engine.guards`` where the backend has a
        ``check`` phase.
        """
        pb, st = self._pipeline, self.stats
        prepared: "queue_lib.Queue" = queue_lib.Queue(maxsize=2)

        def featurize_worker() -> None:
            for idx, (_, _, chunk) in enumerate(plan):
                try:
                    with st.span("featurize.chunk", "featurize_s",
                                 call=call, chunk=idx):
                        X = pb.prepare(chunk, st)
                except BaseException as e:  # noqa: BLE001 — re-raised below
                    prepared.put((idx, e))
                    return
                prepared.put((idx, X))

        worker = threading.Thread(target=featurize_worker, daemon=True,
                                  name="engine-featurize")
        worker.start()
        inflight: List[Tuple[int, Any]] = []   # (plan index, handle|None)
        for k in range(len(plan)):
            with st.span("engine.wait_features", "feature_wait_s",
                         call=call, chunk=k):
                idx, X = prepared.get()
            if isinstance(X, BaseException):
                # worker died: this and all later chunks fall back to
                # the composed serial call in the collect loop
                inflight.extend((j, None) for j in range(idx, len(plan)))
                break
            try:
                with st.span("engine.dispatch", "dispatch_s", call=call,
                             chunk=idx):
                    handle = pb.dispatch(X)
            except BaseException:           # noqa: BLE001 — healed below
                handle = None
            inflight.append((idx, handle))
        worker.join()
        rows: List[Optional[np.ndarray]] = [None] * len(plan)
        for idx, handle in inflight:
            i, take, chunk = plan[idx]
            with st.span("engine.collect", "collect_s", call=call,
                         chunk=idx):
                y = None
                if handle is not None:
                    try:
                        if pb.check is not None:
                            with st.span("engine.guards", "guard_s",
                                         call=call, chunk=idx):
                                pb.check(handle)
                        y = np.asarray(pb.collect(handle))
                    except BaseException:   # noqa: BLE001 — healed below
                        y = None
                if y is None:
                    y = self._eval_backend(chunk)
            if y.shape[0] != len(chunk):
                raise ValueError(
                    f"backend returned {y.shape[0]} rows for "
                    f"{len(chunk)} configs")
            part = y[:take]
            if self.nan_guard and not np.all(np.isfinite(part)):
                part = self._guard_rows(configs[i:i + take], part)
            rows[idx] = part
            self.stats.update(chunks=1)
        return np.concatenate(rows, 0)

    # -- constructors ------------------------------------------------------

    @classmethod
    def from_gnn(cls, two_cfg, params, ds, app,
                 entries: Dict[str, Sequence], *, chunk_size: int = 512,
                 use_kernel: str = "auto", cache: bool = True,
                 devices: int = 1, overlap: Optional[bool] = None
                 ) -> "SurrogateEngine":
        """GNN-surrogate engine (the ApproxPilot fast path).

        Featurizes by table lookup, runs the two-stage model under jit with
        bucketed batch shapes, denormalizes and flips ssim to the
        minimized ``1 - ssim`` objective. The backend is a
        `PipelinedBackend`, so multi-chunk calls overlap host
        featurization with device compute by default (``overlap``, see
        `SurrogateEngine` — disableable for measurement).

        ``devices``: spread chunks over up to this many local devices
        (``0`` = all of them), one whole chunk per device in turn
        (`_over_devices`); rows are bit-identical to ``devices=1`` at any
        width (tests/test_engine_sharded.py).

        ``use_kernel``: "auto" runs the message-passing layers through the
        Pallas `gnn_mp` kernel on TPU for the gcn/gsae architectures (the
        ones the kernel implements) and pure JAX otherwise; "on" forces
        the kernel (interpret-mode off-TPU — correct but slow, used by
        tests) and rejects other architectures; "off" forces pure JAX.
        Whenever the kernel is chosen it must build and match the pure-JAX
        forward on a probe batch within `KERNEL_PARITY_TOL`, or
        construction raises.
        """
        from repro.kernels import ops as kernel_ops

        feat = _ConfigFeaturizer(ds, app, entries)
        sv = getattr(two_cfg, "schema_version", 1)
        if sv != feat.schema.version:
            raise ValueError(
                f"model was trained on feature schema v{sv} but the "
                f"dataset featurizes with v{feat.schema.version} — "
                f"rebuild the stale artifact")
        splice = _probe_splice(feat)
        jax_predict = _make_jax_predict(two_cfg, params, feat.adj, feat.mask,
                                        splice)
        predict, backend = jax_predict, "jax"
        kernel_arch = two_cfg.gnn.arch in ("gcn", "gsae")
        if use_kernel == "on" and not kernel_arch:
            raise ValueError(
                f"use_kernel='on' but the gnn_mp kernel does not support "
                f"arch={two_cfg.gnn.arch!r} (only gcn/gsae)")
        if use_kernel == "on" or (use_kernel == "auto" and kernel_arch
                                  and kernel_ops.on_tpu()):
            kp = _make_kernel_predict(two_cfg, params, feat.adj, feat.mask,
                                      splice=splice)
            Xp, probe = feat.on_device(_probe_configs(feat.sizes))
            probe.check()
            inputs = (Xp, probe.ssim)
            got, want = np.asarray(kp(inputs)), np.asarray(jax_predict(inputs))
            if not np.allclose(got, want, rtol=KERNEL_PARITY_TOL,
                               atol=KERNEL_PARITY_TOL):
                raise RuntimeError(
                    "the gnn_mp kernel path failed the parity check against "
                    f"pure JAX: max |diff| {np.abs(got - want).max():.3g} "
                    f"(tolerance {KERNEL_PARITY_TOL})")
            predict, backend = kp, "pallas"

        n_dev = _resolve_devices(devices)
        on_devices = _over_devices(predict, n_dev)

        def prepare(configs, stats=None):
            # host: lookup + timing sweep; the probe stays on the device
            return feat.on_device(configs, stats)

        def dispatch(features):
            X, probe = features
            return _Dispatched(on_devices((X, probe.ssim)), probe.check)

        def collect(h):
            y = np.asarray(h.out)           # blocks on device compute
            y = ds.denorm_y(y)
            y[:, 3] = 1 - y[:, 3]           # ssim -> 1-ssim (minimize)
            return y

        pb = PipelinedBackend(prepare, dispatch, collect,
                              check=_guard_check(feat), devices=n_dev)
        return cls(pb, backend=backend, chunk_size=chunk_size,
                   fixed_shape=True, cache=cache, overlap=overlap,
                   schema_version=sv)

    @classmethod
    def from_gnn_shared(cls, two_cfg, params, merged, app_name: str,
                        entries: Dict[str, Sequence], *,
                        chunk_size: int = 512, cache: bool = True,
                        devices: int = 1,
                        overlap: Optional[bool] = None
                        ) -> "SurrogateEngine":
        """Per-app view of the cross-app unified surrogate.

        ``merged`` is the `repro.core.dataset.MergedDataset` the shared
        params were fitted on (its `per_app` bookkeeping supplies the
        app's featurizer normalization and y denorm stats); ``params`` is
        ONE shared two-stage model over the merged feature layout. The
        view featurizes configs with the app's own `ConfigFeaturizer` at
        the merged pad width, appends the app-identity one-hot block, and
        denormalizes with the app's y stats — so five scenarios are
        served off one set of trained parameters. ``devices``/``overlap``
        behave exactly as in `from_gnn` (pipelined backend, leading-axis
        sharding).
        """
        from repro.accel import apps as apps_lib
        from repro.core import dataset as ds_lib
        from repro.core import graph as graph_lib

        if app_name not in merged.per_app:
            raise ValueError(f"{app_name!r} not in merged dataset "
                             f"{merged.app_names}")
        ds = merged.per_app[app_name]
        app = apps_lib.APPS[app_name]
        feat = ds_lib.ConfigFeaturizer(ds.graph, app, entries,
                                       merged.n_pad, schema=ds.schema)
        feat.set_norm(ds.x_mean, ds.x_std)
        block = graph_lib.app_block(app_name, feat.mask)      # (N, A)
        # the probe columns lie before the app block, so the app's own
        # featurizer places them in the merged layout too
        jax_predict = _make_jax_predict(two_cfg, params, feat.adj,
                                        feat.mask, _probe_splice(feat))
        n_dev = _resolve_devices(devices)
        on_devices = _over_devices(jax_predict, n_dev)

        def prepare(configs, stats=None):
            X, probe = _featurize_on_device(feat, configs, stats)
            return np.concatenate(
                [X, np.broadcast_to(block, (X.shape[0],) + block.shape)],
                axis=-1), probe

        def dispatch(features):
            Xa, probe = features
            return _Dispatched(
                on_devices((np.ascontiguousarray(Xa), probe.ssim)),
                probe.check)

        def collect(h):
            y = np.asarray(h.out)
            y = ds.denorm_y(y)
            y[:, 3] = 1 - y[:, 3]           # ssim -> 1-ssim (minimize)
            return y

        pb = PipelinedBackend(prepare, dispatch, collect,
                              check=_guard_check(feat), devices=n_dev)
        return cls(pb, backend="jax-shared", chunk_size=chunk_size,
                   fixed_shape=True, cache=cache, overlap=overlap,
                   schema_version=feat.schema.version)

    @classmethod
    def from_gnn_ensemble(cls, ens, ds, app, entries: Dict[str, Sequence],
                          *, chunk_size: int = 512, cache: bool = True,
                          devices: int = 1,
                          overlap: Optional[bool] = None
                          ) -> "SurrogateEngine":
        """Ensemble-GNN engine: objectives = denormalized ensemble MEAN,
        plus a per-objective ensemble-std uncertainty block (columns
        [obj_cols:]) for the DSE acquisition path.

        `ens` is a `repro.core.training.EnsembleParams`; every member
        group runs as one vmapped jit over the member axis (pure-JAX path
        — the Pallas gnn_mp dispatch stays single-model for now). The std
        is denormalized with the same per-target scale as the mean; the
        ssim flip (1 - ssim) leaves its std unchanged. ``devices`` spreads
        chunks over devices as in `from_gnn`;
        ``overlap`` pipelines featurization exactly as in `from_gnn` —
        dispatch enqueues every member group before collect blocks.
        """
        import jax
        import jax.numpy as jnp
        from repro.core import models as models_lib

        feat = _ConfigFeaturizer(ds, app, entries)
        A = jnp.asarray(feat.adj)
        m_row = jnp.asarray(feat.mask)
        splice = _probe_splice(feat)

        group_fns = []
        for g_cfg, params in ens.groups:
            @jax.jit
            def gf(X, g_cfg=g_cfg, params=params):
                X = splice(X)
                B = X.shape[0]
                adj = jnp.broadcast_to(A, (B,) + A.shape)
                mask = jnp.broadcast_to(m_row, (B,) + m_row.shape)
                with jax.default_matmul_precision("highest"):
                    return jax.vmap(lambda p: models_lib.predict(
                        g_cfg, p, adj, X, mask)[0])(params)
            group_fns.append(gf)

        n_obj = len(models_lib.TARGETS)
        n_dev = _resolve_devices(devices)
        on_devices = _over_devices(
            lambda inputs: [gf(inputs) for gf in group_fns], n_dev)

        def prepare(configs, stats=None):
            return feat.on_device(configs, stats)

        def dispatch(features):
            X, probe = features
            return _Dispatched(on_devices((X, probe.ssim)), probe.check)

        def collect(h):
            Y = np.concatenate([np.asarray(a) for a in h.out], 0)
            mean = ds.denorm_y(Y.mean(0))
            std = Y.std(0) * np.asarray(ds.y_std)
            mean[:, 3] = 1 - mean[:, 3]     # ssim -> 1-ssim (minimize)
            return np.concatenate([mean, std], 1)

        pb = PipelinedBackend(prepare, dispatch, collect,
                              check=_guard_check(feat), devices=n_dev)
        return cls(pb, backend="gnn-ensemble", chunk_size=chunk_size,
                   fixed_shape=True, cache=cache, obj_cols=n_obj,
                   overlap=overlap, schema_version=feat.schema.version)

    @classmethod
    def from_rforest(cls, rf_models: Dict[int, "object"], ds, app,
                     entries: Dict[str, Sequence], *,
                     chunk_size: int = 4096,
                     cache: bool = True) -> "SurrogateEngine":
        """Random-forest engine (the AutoAX baseline).

        Uses the same vectorized featurizer, then the per-target forests on
        the flat (masked, normalized) feature vectors — matching
        `AccelDataset.flat_features` exactly, where the previous inline
        evaluator fed un-masked padding rows at DSE time.
        """
        feat = _ConfigFeaturizer(ds, app, entries)
        us = feat.schema.sl("unit_stats")

        def batch_fn(configs):
            X = feat(configs)[:, :, us].reshape(len(configs), -1)
            preds = np.stack(
                [rf_models[i].predict(X) * ds.y_std[i] + ds.y_mean[i]
                 for i in range(4)], 1)
            preds[:, 3] = 1 - preds[:, 3]
            return preds

        return cls(batch_fn, backend="rforest", chunk_size=chunk_size,
                   fixed_shape=False, cache=cache,
                   schema_version=feat.schema.version)

    @classmethod
    def from_oracle(cls, app, entries: Dict[str, Sequence], inp, exact_out,
                    *, cache: bool = True,
                    chunk_size: int = 256) -> "SurrogateEngine":
        """Synthesis-oracle engine (ground truth), served by the batched
        labeling path: vectorized `batch_oracle.synthesize_batch` PPA +
        the config-batched LUT functional model for SSIM. Fixed-shape
        chunking keeps the functional model's jit cache bounded."""
        from repro.accel import batch_oracle

        def batch_fn(configs):
            return batch_oracle.objective_rows(app, entries, configs, inp,
                                               exact_out, chunk=chunk_size)

        return cls(batch_fn, backend="oracle", chunk_size=chunk_size,
                   fixed_shape=True, cache=cache)


def _probe_configs(sizes: Sequence[int], n: int = 4) -> List[Config]:
    """Small deterministic config set for the kernel parity check."""
    rng = np.random.default_rng(0)
    return [tuple(int(rng.integers(0, s)) for s in sizes) for _ in range(n)]
