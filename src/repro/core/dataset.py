"""Dataset construction for the PPA/accuracy prediction models (Sec III-B1).

Random sampling over the (pruned) design space with symmetric-structure
deduplication; labels from the simulated synthesis oracle (PPA + critical
path) and the vectorized functional model (SSIM on the image set).

`build` labels through the batched ground-truth engine
(`repro.accel.batch_oracle.synthesize_batch` + the config-batched LUT
functional model `apps.accuracy_ssim_batch`): the whole sample block is
labeled as (B, ...) array programs instead of a per-config Python loop.
`build_loop` is the scalar reference — tests/test_batch_oracle.py asserts
the labels are equivalent (bit-identical critical bits, float-tolerance
PPA/SSIM). Feature tensors are assembled by `ConfigFeaturizer`, which
caches every config-independent column.

Paper scale: 55k/105k/105k samples, 90/10 split. CPU-scaled defaults are
smaller; `pipeline.PipelineConfig.paper_faithful(app)` uses the original
sizes.
"""
from __future__ import annotations

import dataclasses
import threading
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.accel import apps as apps_lib
from repro.accel import library as lib
from repro.accel import synth
from repro.core import graph as graph_lib
from repro.data import images as images_lib

# function-level symmetric tap groups (equal coefficients / equivalent
# streams) used for duplicate elimination — see DESIGN.md.
SYMMETRY = {
    "gaussian": (("m0", "m2", "m6", "m8"), ("m1", "m3", "m5", "m7")),
    "sobel": (),
    "kmeans": (),
    "dct8": (),     # butterfly lanes see distinct coefficient schedules
    "fir15": (),    # every tap pair has a distinct coefficient
}


@dataclass
class AccelDataset:
    app_name: str
    graph: graph_lib.SimpleGraph
    adj: np.ndarray          # (B,N,N) normalized
    x: np.ndarray            # (B,N,F) crit bit zeroed
    mask: np.ndarray         # (B,N)
    unit_mask: np.ndarray    # (B,N) 1 on arithmetic-unit nodes
    y: np.ndarray            # (B,4) normalized [area,power,latency,ssim]
    y_raw: np.ndarray
    crit: np.ndarray         # (B,N) ground truth critical-path bits
    configs: List[Tuple[int, ...]]
    y_mean: np.ndarray
    y_std: np.ndarray
    x_mean: np.ndarray
    x_std: np.ndarray
    # feature-schema version of `x` (graph.SCHEMAS); datasets pickled
    # before the schema refactor deserialize without the field and are
    # treated as v1 via `schema_of`
    schema_version: int = 1

    @property
    def schema(self) -> graph_lib.FeatureSchema:
        return graph_lib.schema_for(getattr(self, "schema_version", 1))

    # Every config of one accelerator shares graph topology, so adj /
    # mask / unit_mask are (usually) B identical rows; persisting all B
    # would dominate the artifact-store pickle at paper scale (55k-105k
    # samples). Collapse constant-row tensors to one row + count on
    # pickle and rebroadcast on load; the transient featurizer cache
    # (`featurizer_for`) is rebuildable and is dropped.
    def __getstate__(self):
        state = self.__dict__.copy()
        state.pop("_featurizers", None)
        for k in ("adj", "mask", "unit_mask"):
            v = state[k]
            if isinstance(v, np.ndarray) and v.shape[0] > 1 and \
                    (v == v[:1]).all():
                state[k] = ("__const_rows__", np.ascontiguousarray(v[0]),
                            v.shape[0])
        return state

    def __setstate__(self, state):
        for k, v in state.items():
            if isinstance(v, tuple) and len(v) == 3 and \
                    v[0] == "__const_rows__":
                state[k] = np.broadcast_to(
                    v[1], (v[2],) + v[1].shape).copy()
        self.__dict__.update(state)

    def split(self, frac: float = 0.9):
        n = int(len(self.y) * frac)
        tr = dataclasses.replace(
            self, adj=self.adj[:n], x=self.x[:n], mask=self.mask[:n],
            unit_mask=self.unit_mask[:n], y=self.y[:n], y_raw=self.y_raw[:n],
            crit=self.crit[:n], configs=self.configs[:n])
        te = dataclasses.replace(
            self, adj=self.adj[n:], x=self.x[n:], mask=self.mask[n:],
            unit_mask=self.unit_mask[n:], y=self.y[n:], y_raw=self.y_raw[n:],
            crit=self.crit[n:], configs=self.configs[n:])
        return tr, te

    def denorm_y(self, y: np.ndarray) -> np.ndarray:
        return y * self.y_std + self.y_mean

    # flat per-graph feature vector for the random-forest baseline
    def flat_features(self) -> np.ndarray:
        B = self.x.shape[0]
        us = self.schema.sl("unit_stats")
        return (self.x[..., us] * self.mask[..., None]).reshape(B, -1)


@dataclass
class MergedDataset:
    """Union of per-app datasets on a common pad width, for the cross-app
    unified surrogate.

    Feature rows are each app's *own-normalized* features (per-app x
    stats: standardized columns are scale-free across apps) with the
    one-hot app-identity block of `graph.APP_VOCAB` appended — so the
    feature dim is ``graph.MERGED_FEATURE_DIM`` for ANY app subset and
    leave-one-app-out training keeps identical parameter shapes. Targets
    stay normalized per app (per-app y stats are the bookkeeping needed to
    denormalize a prediction for its app — `denorm_rows` / the engine's
    per-app views). Rows are shuffled at merge time so `split` produces
    app-mixed train/test sets; `app_ids` tracks provenance.

    Exposes the same tensor attributes as `AccelDataset` (adj, x, mask,
    unit_mask, y, y_raw, crit) plus `split`, so `training.fit_two_stage`
    consumes it unchanged.
    """
    app_names: Tuple[str, ...]
    adj: np.ndarray          # (B,N,N) normalized, N = common n_pad
    x: np.ndarray            # (B,N,MERGED_FEATURE_DIM) crit bit zeroed
    mask: np.ndarray         # (B,N)
    unit_mask: np.ndarray    # (B,N)
    y: np.ndarray            # (B,4) per-app normalized
    y_raw: np.ndarray        # (B,4)
    crit: np.ndarray         # (B,N)
    app_ids: np.ndarray      # (B,) index into app_names
    configs: List[Tuple[int, ...]]
    per_app: Dict[str, "AccelDataset"]

    _ROW_FIELDS = ("adj", "x", "mask", "unit_mask", "y", "y_raw", "crit",
                   "app_ids")

    def _take(self, sel) -> "MergedDataset":
        """Row-restriction by slice or boolean mask — the ONE place the
        per-row fields are enumerated (split/view stay in sync)."""
        kw = {k: getattr(self, k)[sel] for k in self._ROW_FIELDS}
        if isinstance(sel, slice):
            kw["configs"] = self.configs[sel]
        else:
            kw["configs"] = [c for c, keep in zip(self.configs, sel)
                             if keep]
        return dataclasses.replace(self, **kw)

    def split(self, frac: float = 0.9):
        n = int(len(self.y) * frac)
        return self._take(slice(None, n)), self._take(slice(n, None))

    def view(self, app_name: str) -> "MergedDataset":
        """Row-restriction to one app (per-app evaluation / fine-tuning)."""
        return self._take(self.app_ids == self.app_names.index(app_name))

    def denorm_rows(self, y: np.ndarray,
                    app_ids: Optional[np.ndarray] = None) -> np.ndarray:
        """Denormalize per row with each row's own app stats."""
        ids = self.app_ids if app_ids is None else app_ids
        mean = np.stack([self.per_app[a].y_mean for a in self.app_names])
        std = np.stack([self.per_app[a].y_std for a in self.app_names])
        return y * std[ids] + mean[ids]

    @property
    def n_pad(self) -> int:
        return self.x.shape[1]


def _pad_nodes(a: np.ndarray, n_pad: int, is_adj: bool = False
               ) -> np.ndarray:
    """Zero-pad the node axis (axis 1, and axis 2 when ``is_adj``) to
    n_pad. The adjacency case is an explicit flag: shape sniffing would
    misread a (B, N, F) feature tensor with N == F."""
    n = a.shape[1]
    if n == n_pad:
        return a
    if n > n_pad:
        raise ValueError(f"cannot pad {n} nodes down to {n_pad}")
    widths = [(0, 0), (0, n_pad - n)] + [(0, 0)] * (a.ndim - 2)
    if is_adj:
        widths[2] = (0, n_pad - n)
    return np.pad(a, widths)


def merge(datasets: Dict[str, "AccelDataset"], n_pad: Optional[int] = None,
          shuffle_seed: int = 0) -> MergedDataset:
    """Merge per-app datasets into one cross-app training set.

    ``datasets`` maps app name -> `AccelDataset` (any subset of
    `graph.APP_VOCAB`, including a single app — used by the fine-tune leg
    of `training.evaluate_transfer`). All inputs must share the base
    feature layout (`graph.FEATURE_DIM`); node counts may differ and are
    padded to a common ``n_pad`` (default: the widest input).
    """
    if not datasets:
        raise ValueError("merge() needs at least one dataset")
    names = tuple(sorted(datasets, key=graph_lib.APP_VOCAB.index))
    versions = {getattr(datasets[a], "schema_version", 1) for a in names}
    if len(versions) != 1:
        raise ValueError(f"merge() needs one feature-schema version, got "
                         f"{sorted(versions)} — rebuild the stale datasets")
    schema = graph_lib.schema_for(versions.pop())
    dims = {datasets[a].x.shape[-1] for a in names}
    if dims != {schema.dim}:
        raise ValueError(f"merge() expects base feature dim {schema.dim} "
                         f"(schema v{schema.version}), got {sorted(dims)}")
    n_pad = n_pad or max(datasets[a].x.shape[1] for a in names)
    adjs, xs, masks, umasks, ys, yraws, crits, ids, cfgs = \
        [], [], [], [], [], [], [], [], []
    for i, a in enumerate(names):
        ds = datasets[a]
        m = _pad_nodes(ds.mask, n_pad)
        adjs.append(_pad_nodes(ds.adj, n_pad, is_adj=True))
        xs.append(graph_lib.with_app_block(_pad_nodes(ds.x, n_pad), m, a))
        masks.append(m)
        umasks.append(_pad_nodes(ds.unit_mask, n_pad))
        ys.append(ds.y)
        yraws.append(ds.y_raw)
        crits.append(_pad_nodes(ds.crit, n_pad))
        ids.append(np.full(len(ds.y), i, np.int64))
        cfgs.extend(ds.configs)
    perm = np.random.default_rng(shuffle_seed).permutation(
        sum(len(v) for v in ids))
    cat = lambda parts: np.concatenate(parts, 0)[perm]
    cfgs = [cfgs[j] for j in perm]
    return MergedDataset(names, cat(adjs), cat(xs), cat(masks), cat(umasks),
                         cat(ys), cat(yraws), cat(crits), cat(ids), cfgs,
                         {a: datasets[a] for a in names})


def canonical(app: apps_lib.AccelDef, config: Dict[str, int]
              ) -> Tuple[int, ...]:
    """Sort instance indices inside each symmetric group -> canonical key."""
    cfg = dict(config)
    for group in SYMMETRY.get(app.name, ()):
        vals = sorted(cfg[g] for g in group)
        for g, v in zip(group, vals):
            cfg[g] = v
    return tuple(cfg[n.id] for n in app.unit_nodes)


def sample_configs(app: apps_lib.AccelDef, n: int, seed: int = 0,
                   lib_entries: Optional[Dict[str, Sequence]] = None,
                   dedup: bool = True) -> List[Tuple[int, ...]]:
    """Random (deduplicated) configuration sample over the design space.

    May return FEWER than ``n`` configs: with ``dedup=True`` on a design
    space smaller than (or close to) ``n``, rejection sampling is capped
    at 50·n tries so a saturated space cannot loop forever. The shortfall
    is reported via `warnings.warn` — callers that require exactly ``n``
    rows must check ``len()`` of the result.
    """
    rng = np.random.default_rng(seed)
    entries = lib_entries or {k.kind: lib.build_library(k.kind)
                              for k in app.unit_nodes}
    sizes = [len(entries[k.kind]) for k in app.unit_nodes]
    seen = set()
    out: List[Tuple[int, ...]] = []
    tries = 0
    while len(out) < n and tries < 50 * n:
        tries += 1
        cfg = {node.id: int(rng.integers(0, s))
               for node, s in zip(app.unit_nodes, sizes)}
        key = canonical(app, cfg) if dedup else tuple(
            cfg[node.id] for node in app.unit_nodes)
        if dedup and key in seen:
            continue
        seen.add(key)
        out.append(key if dedup else tuple(cfg[node.id]
                                           for node in app.unit_nodes))
    if len(out) < n:
        import warnings
        warnings.warn(
            f"sample_configs({app.name!r}): dedup retry cap (50*n="
            f"{50 * n} tries) reached with {len(out)}/{n} unique configs "
            f"— the (canonicalized) design space is likely smaller than "
            f"n; proceeding with {len(out)} samples", stacklevel=2)
    return out


class ConfigFeaturizer:
    """Config -> node-feature tensors with cached constant columns.

    Every configuration of one accelerator shares graph topology, so the
    normalized adjacency, mask, fixed-node rows, one-hot kind columns and
    padding are per-graph constants; only the unit-stats block of the
    arithmetic-unit rows (area, power, latency, mae, mre, mse, wce, approx
    level) depends on the chosen library entry, the critical-path column
    on the oracle, and — under schema v2 — the dynamic timing block on the
    batched timing oracle (`batch_oracle.timing_batch`: per-node slack,
    criticality, and DAG-propagated error mass). Static columns are filled
    by table lookup / assignment, dynamic ones by one vectorized timing
    sweep per batch — O(batch) numpy ops instead of rebuilding every row
    in Python.

    `raw` feeds `build` (labels known, stats not yet); `normalized` feeds
    the DSE hot path (`features_for_configs`, the engine featurizer) and
    is bit-identical to the build path's rows (tests/test_engine.py,
    tests/test_feature_schema.py): both paths cast the float64 timing
    sweep to float32 once and then apply the elementwise-identical
    standardization.
    """

    def __init__(self, g: graph_lib.SimpleGraph, app: apps_lib.AccelDef,
                 entries: Dict[str, Sequence], n_pad: int,
                 schema: Optional[graph_lib.FeatureSchema] = None):
        self.schema = schema or graph_lib.ACTIVE_SCHEMA
        self.n_pad = n_pad
        self.n_nodes = len(g.node_ids)
        self.sizes = [len(entries[n.kind]) for n in app.unit_nodes]
        self._graph = g
        self._app = app
        self._entries = entries
        self.dynamic = bool(self.schema.dynamic_fields)
        self._members: Optional[List[np.ndarray]] = None
        # `normalized_on_device` runs on the engine's featurize worker
        # thread (the overlap pipeline) while other engines sharing this
        # featurizer (`featurizer_for` caches per dataset) may call it
        # concurrently; the lock makes the lazy member-index build
        # single-shot instead of merely idempotent
        self._members_lock = threading.Lock()
        # the probe left on the device (`normalized_on_device`), built on
        # first use; two threads may each build one, and either serves
        self._prober = None
        choice0 = {n.id: entries[n.kind][0] for n in app.unit_nodes}
        xf0 = graph_lib.node_features(g, app, choice0, crit_nodes=None,
                                      schema=self.schema)
        A, X0, M = graph_lib.pad_batch([g.adj], [xf0], n_pad)
        self.adj = A[0]                           # (N, N) normalized
        self.mask = M[0]                          # (N,)
        self.base_raw = X0[0]                     # (N, F), unit rows dummy
        self.gidx = [g.node_ids.index(n.id) for n in app.unit_nodes]
        self._us = self.schema.sl("unit_stats")
        kind_tables: Dict[str, np.ndarray] = {}
        self.tables_raw: List[np.ndarray] = []
        for node in app.unit_nodes:
            if node.kind not in kind_tables:
                kind_tables[node.kind] = np.asarray(
                    [[e.area, e.power, e.latency, e.mae, e.mre, e.mse,
                      e.wce, float(e.inst.level)]
                     for e in entries[node.kind]], np.float32)
            self.tables_raw.append(kind_tables[node.kind])
        self._norm = None

    # -- dynamic timing block ----------------------------------------------

    def _member_index(self) -> List[np.ndarray]:
        """Per graph node: app-node positions of its merged members in the
        compiled DAG's node order (lazy — needs the batch oracle)."""
        with self._members_lock:
            if self._members is None:
                from repro.accel import batch_oracle
                ca = batch_oracle.compile_app(self._app.name)
                pos = {nid: a for a, nid in enumerate(ca.node_ids)}
                members = [
                    np.asarray([pos[m]
                                for m in self._graph.merged_from[i]],
                               np.int64) for i in range(self.n_nodes)]
                # singleton fast path: one gather covers every unmerged
                # node; only merged fixed nodes need a per-node reduction
                self._first = np.asarray([m[0] for m in members],
                                         np.int64)
                self._multi = [i for i, m in enumerate(members)
                               if len(m) > 1]
                self._members = members
            return self._members

    @property
    def _has_probe(self) -> bool:
        """Whether this featurizer fills the functional-probe columns."""
        return self.dynamic and any(f in apps_lib.PROBE_FIELDS
                                    for f in self.schema.dynamic_fields)

    def dynamic_raw(self, C: np.ndarray) -> np.ndarray:
        """(B, n_graph_nodes, n_dyn) float32 dynamic timing features, for
        the build path (`raw`).

        One `batch_oracle.timing_batch` sweep per batch, reduced onto the
        (possibly merged) graph nodes per `graph.DYNAMIC_REDUCE` and
        log1p-compressed where the schema says so (`_dynamic_block`, which
        the DSE hot path `normalized_on_device` shares), and the blocking
        `batch_oracle.probe_batch` (the same compiled programs as the hot
        path's `batch_oracle.DeviceProber`). The sweep and the probe run in
        the profiler spans ``featurize.timing`` and ``featurize.probe``.
        """
        from repro.accel import batch_oracle
        with jax.profiler.TraceAnnotation("featurize.timing"):
            rep = batch_oracle.timing_batch(self._app, self._entries, C)
        if self._has_probe:
            with jax.profiler.TraceAnnotation("featurize.probe"):
                rep.update(batch_oracle.probe_batch(self._app,
                                                    self._entries, C))
        return self._dynamic_block(rep, C.shape[0])

    def _dynamic_block(self, rep: Dict[str, np.ndarray],
                       B: int) -> np.ndarray:
        """The dynamic columns from the oracle's report ``rep``; a probe
        field missing from ``rep`` (left on the device) stays 0."""
        fields = self.schema.dynamic_fields
        members = self._member_index()
        out = np.zeros((B, self.n_nodes, len(fields)), np.float32)
        for f_idx, f in enumerate(fields):
            if f in apps_lib.PROBE_FIELDS:
                # graph-level probe distortion: one value per config,
                # broadcast across nodes (padding rows stay base-valued)
                if f in rep:
                    out[:, :, f_idx] = rep[f][:, None]
                continue
            col = rep[f]                             # (B, n_app_nodes)
            take_min = graph_lib.DYNAMIC_REDUCE[f] == "min"
            v = col[:, self._first]                  # (B, n_graph_nodes)
            for i in self._multi:
                mem = members[i]
                v[:, i] = (col[:, mem].min(1) if take_min
                           else col[:, mem].max(1))
            if f in graph_lib._LOG1P_FIELDS:
                v = np.log1p(v)
            out[:, :, f_idx] = v
        return out

    # -- feature assembly --------------------------------------------------

    def raw(self, configs, crit: Optional[np.ndarray] = None) -> np.ndarray:
        """(B, n_pad, F) un-normalized features; ``crit`` is an optional
        (B, n_graph_nodes) critical-bit block from the batch oracle."""
        C = np.asarray(configs, np.int64).reshape(-1, len(self.gidx))
        X = np.broadcast_to(self.base_raw,
                            (C.shape[0],) + self.base_raw.shape).copy()
        for j, gj in enumerate(self.gidx):
            X[:, gj, self._us] = self.tables_raw[j][C[:, j]]
        if self.dynamic:
            X[:, :self.n_nodes, self.schema.dynamic_slice] = \
                self.dynamic_raw(C)
        if crit is not None:
            X[:, :self.n_nodes, self.schema.crit_index] = crit
        return X

    def set_norm(self, x_mean: np.ndarray, x_std: np.ndarray) -> None:
        base = ((self.base_raw - x_mean) / x_std
                * self.mask[..., None]).astype(np.float32)
        mu8 = x_mean[self._us].astype(np.float32)
        sd8 = x_std[self._us].astype(np.float32)
        tables = [((t - mu8) / sd8).astype(np.float32)
                  for t in self.tables_raw]
        dyn = self.schema.dynamic_slice
        mu_d = np.asarray(x_mean[dyn], np.float32)
        sd_d = np.asarray(x_std[dyn], np.float32)
        self._norm = (base, tables, mu_d, sd_d)

    @property
    def probe_guards(self) -> bool:
        """Whether the functional probe reads truth tables, and so has
        LUT-domain guards to check: it runs and the app has a tabulated
        unit kind (`library.LUT_DOMAINS`)."""
        return self._has_probe and any(n.kind in lib.LUT_DOMAINS
                                       for n in self._app.unit_nodes)

    def normalized(self, configs, stats=None) -> np.ndarray:
        """(B, n_pad, F) features normalized with the dataset stats
        (``stats``: the engine's `EngineStats`, into whose ``timing_s``
        and ``probe_s`` the ``featurize.timing`` and ``featurize.probe``
        spans add their durations).

        `normalized_on_device` with the probe read back: its LUT guards
        checked and its SSIM standardized into the probe columns on the
        host, by the float32 expression of `probe_columns`. Together with
        the float32 cast + elementwise standardization of the other
        dynamic columns, that is what the build path applies to the whole
        raw tensor -> bit-identical rows. The read-back is a second
        ``featurize.probe`` span, after the timing sweep.
        """
        X, probe = self.normalized_on_device(configs, stats)
        if probe.ssim:
            with _span(stats, "featurize.probe", "probe_s"):
                probe.check()
                for (col, mu, sd), s in zip(self.probe_columns(),
                                            probe.ssim):
                    X[:, :self.n_nodes, col] = \
                        (((1 - np.asarray(s)) - mu) / sd)[:, None]
        return X

    def normalized_on_device(self, configs, stats=None):
        """`normalized` for a consumer that runs on the device (the GNN
        engine's forward): the functional probe is dispatched there and
        its result never read back.

        Returns ``(X, probe)``. ``X`` equals `normalized` in every column
        but the probe's (their real graph rows hold a placeholder);
        ``probe`` is the `batch_oracle.DeviceProbe` whose SSIM the
        consumer standardizes into those columns (`probe_columns`) and
        whose ``check()`` it runs before trusting its output. The probe is
        dispatched before the timing sweep, so the device runs it while
        the host sweeps; its ``featurize.probe`` span (``probe_s``) covers
        building the config block and dispatching, and its ``lut_reads``
        argument, added to ``stats.lut_reads``, counts the truth-table
        entries the probe reads (`batch_oracle.DeviceProber.lut_reads`
        per configuration); its ``lut_square_reads`` argument, added to
        ``stats.lut_square_reads``, those of them squarers read from their
        tables' diagonals.
        """
        from repro.accel import batch_oracle
        if self._norm is None:
            raise RuntimeError("call set_norm(x_mean, x_std) first")
        base, tables, mu_d, sd_d = self._norm
        C = np.asarray(configs, np.int64).reshape(-1, len(self.gidx))
        X = np.broadcast_to(base, (C.shape[0],) + base.shape).copy()
        for j, gj in enumerate(self.gidx):
            X[:, gj, self._us] = tables[j][C[:, j]]
        probe = batch_oracle.DeviceProbe((), lambda: None)
        if self._has_probe:
            if self._prober is None:
                self._prober = batch_oracle.DeviceProber(self._app,
                                                         self._entries)
            reads = self._prober.lut_reads * C.shape[0]
            squares = self._prober.lut_square_reads * C.shape[0]
            with _span(stats, "featurize.probe", "probe_s",
                       lut_reads=reads, lut_square_reads=squares):
                probe = self._prober(C)
            if stats is not None:
                stats.update(lut_reads=reads, lut_square_reads=squares)
        if self.dynamic:
            with _span(stats, "featurize.timing", "timing_s"):
                rep = batch_oracle.timing_batch(self._app, self._entries, C)
            X[:, :self.n_nodes, self.schema.dynamic_slice] = \
                (self._dynamic_block(rep, C.shape[0]) - mu_d) / sd_d
        return X, probe

    def probe_columns(self) -> Tuple[Tuple[int, np.float32, np.float32],
                                     ...]:
        """``(column, mean, std)`` of each probe field, in
        `apps.PROBE_SIZES` order: the float32 standardization `normalized`
        applies, ``((1 - ssim) - mean) / std``. Empty when this featurizer
        fills no probe columns."""
        if not self._has_probe:
            return ()
        fields = self.schema.dynamic_fields
        _, _, mu_d, sd_d = self._norm
        return tuple((self.schema.col("timing", f), mu_d[fields.index(f)],
                      sd_d[fields.index(f)]) for f in apps_lib.PROBE_FIELDS)


def _span(stats, name: str, counter: str, **args):
    """``stats.span(name, counter, **args)``, or the bare profiler span
    when no engine counts this featurization (dataset building)."""
    if stats is None:
        return jax.profiler.TraceAnnotation(name, **args)
    return stats.span(name, counter, **args)


def _entries_sig(entries: Dict[str, Sequence]) -> Tuple:
    return tuple(sorted((k, tuple(e.inst.name for e in v))
                        for k, v in entries.items()))


def build(app_name: str, n_samples: int = 2000, seed: int = 0,
          n_images: int = 4, img_size: int = 64,
          lib_entries: Optional[Dict[str, Sequence]] = None,
          simplify_graph: bool = True, n_pad: int = 32,
          label_chunk: int = 256) -> AccelDataset:
    """Sample ``n_samples`` configurations and label them with the batched
    oracle (`build_loop` is the scalar reference)."""
    return _build(app_name, n_samples, seed, n_images, img_size,
                  lib_entries, simplify_graph, n_pad,
                  lambda *a: _label_batched(*a, chunk=label_chunk))


def build_loop(app_name: str, n_samples: int = 2000, seed: int = 0,
               n_images: int = 4, img_size: int = 64,
               lib_entries: Optional[Dict[str, Sequence]] = None,
               simplify_graph: bool = True,
               n_pad: int = 32) -> AccelDataset:
    """Reference for `build`: the same dataset, labeled by one scalar
    oracle and functional-model call per configuration."""
    return _build(app_name, n_samples, seed, n_images, img_size,
                  lib_entries, simplify_graph, n_pad, _label_loop)


def _label_batched(app, g, entries, configs, inp, exact_out, n_pad, *,
                   chunk: int):
    """(A, X, M, y_raw) by the batched oracle and the config-batched LUT
    functional model."""
    from repro.accel import batch_oracle
    C = np.asarray(configs, np.int64)
    rep = batch_oracle.synthesize_batch(app, entries, C)
    acc = apps_lib.accuracy_ssim_batch(app, entries, C, inp, exact_out,
                                       chunk=chunk)
    y_raw = np.stack([rep["area"], rep["power"], rep["latency"], acc],
                     axis=1).astype(np.float32)
    # map app-node critical bits onto the (possibly merged) graph nodes
    pos = {nid: a for a, nid in enumerate(rep["node_ids"])}
    memb = np.zeros((len(g.node_ids), len(rep["node_ids"])), np.float32)
    for i, members in enumerate(g.merged_from):
        for m in members:
            memb[i, pos[m]] = 1.0
    crit_graph = (rep["crit"].astype(np.float32)
                  @ memb.T > 0).astype(np.float32)
    feat = ConfigFeaturizer(g, app, entries, n_pad)
    X = feat.raw(C, crit=crit_graph)
    A = np.broadcast_to(feat.adj, (len(configs),) + feat.adj.shape).copy()
    M = np.broadcast_to(feat.mask, (len(configs),) + feat.mask.shape).copy()
    return A, X, M, y_raw


def _label_loop(app, g, entries, configs, inp, exact_out, n_pad):
    """(A, X, M, y_raw) by one scalar oracle + functional-model call per
    config."""
    schema = graph_lib.ACTIVE_SCHEMA
    adjs, feats, ys = [], [], []
    for cfg_idx in configs:
        choice = {node.id: entries[node.kind][i]
                  for node, i in zip(app.unit_nodes, cfg_idx)}
        rep = synth.synthesize(app, choice)
        acc = apps_lib.accuracy_ssim(app, choice, inp, exact_out)
        timing = (synth.static_timing(app, choice)["nodes"]
                  if schema.dynamic_fields else None)
        xf = graph_lib.node_features(g, app, choice,
                                     crit_nodes=rep["critical_nodes"],
                                     timing=timing, schema=schema)
        adjs.append(g.adj)
        feats.append(xf)
        ys.append([rep["area"], rep["power"], rep["latency"], acc])
    A, X, M = graph_lib.pad_batch(adjs, feats, n_pad)
    return A, X, M, np.asarray(ys, np.float32)


def _build(app_name, n_samples, seed, n_images, img_size, lib_entries,
           simplify_graph, n_pad, label) -> AccelDataset:
    app = apps_lib.APPS[app_name]
    g = graph_lib.build_graph(app, simplify=simplify_graph)
    entries = lib_entries or {k: lib.build_library(k) for k in
                              {n.kind for n in app.unit_nodes}}

    imgs = images_lib.image_set(n_images, img_size)
    if app_name == "kmeans":
        inp = jnp.asarray(imgs.astype(np.int32))
    else:
        inp = jnp.asarray(images_lib.gray(imgs))
    exact_out = app.run(apps_lib.make_impls(app, apps_lib.exact_choice(app)),
                        inp)

    configs = sample_configs(app, n_samples, seed, lib_entries=entries)
    A, X, M, y_raw = label(app, g, entries, configs, inp, exact_out, n_pad)

    schema = graph_lib.ACTIVE_SCHEMA
    crit = X[..., schema.crit_index].copy()
    X[..., schema.crit_index] = 0.0
    unit_mask = np.zeros_like(M)
    unit_ids = {n.id for n in app.unit_nodes}
    for j, nid in enumerate(g.node_ids):
        if nid in unit_ids:
            unit_mask[:, j] = 1.0
    # normalize
    y_mean, y_std = y_raw.mean(0), y_raw.std(0) + 1e-6
    y = (y_raw - y_mean) / y_std
    x_mean = X.reshape(-1, X.shape[-1]).mean(0)
    x_std = X.reshape(-1, X.shape[-1]).std(0) + 1e-6
    # one-hot / crit-bit columns stay raw; the schema says which
    keep = schema.normalize_mask()
    x_mean[~keep] = 0.0
    x_std[~keep] = 1.0
    Xn = (X - x_mean) / x_std * M[..., None]
    return AccelDataset(app_name, g, A, Xn, M, unit_mask, y, y_raw, crit,
                        configs, y_mean, y_std, x_mean, x_std,
                        schema_version=schema.version)


def featurizer_for(ds: AccelDataset, app: apps_lib.AccelDef,
                   entries: Dict[str, Sequence]) -> ConfigFeaturizer:
    """Get-or-build the dataset's normalized featurizer (cached on ``ds``
    per library signature, so repeated DSE calls reuse the constant
    columns instead of rebuilding every feature row)."""
    cache = getattr(ds, "_featurizers", None)
    if cache is None:
        cache = {}
        ds._featurizers = cache
    key = _entries_sig(entries)
    feat = cache.get(key)
    if feat is None:
        feat = ConfigFeaturizer(ds.graph, app, entries, ds.x.shape[1],
                                schema=ds.schema)
        feat.set_norm(ds.x_mean, ds.x_std)
        cache[key] = feat
    return feat


def features_for_configs(ds: AccelDataset, app: apps_lib.AccelDef,
                         entries: Dict[str, Sequence],
                         configs: Sequence[Tuple[int, ...]]
                         ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Surrogate-input tensors for arbitrary configs (DSE hot path)."""
    feat = featurizer_for(ds, app, entries)
    Xn = feat.normalized(configs)
    B = Xn.shape[0]
    A = np.broadcast_to(feat.adj, (B,) + feat.adj.shape).copy()
    M = np.broadcast_to(feat.mask, (B,) + feat.mask.shape).copy()
    return A, Xn, M
