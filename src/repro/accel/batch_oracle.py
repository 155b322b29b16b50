"""Batched ground-truth labeling: the synthesis oracle as (B, N) arrays.

`synth.synthesize` walks a networkx DAG per configuration — fine for one
design, the bottleneck for paper-scale dataset construction (55k-105k
oracle-labeled samples per accelerator). This module precompiles each
app's DAG once (topologically-levelled edge groups, fanout wire delays,
fixed-component PPA sums) and evaluates a whole (B, n_units) block of
configurations in broadcast float64 NumPy:

  area/power  — fixed-component sums + per-unit table lookups
  latency     — levelled longest-path sweep over conflict-free edge groups
  critical    — the same sweep backwards (required-time propagation),
                bit-for-bit identical node sets vs the scalar oracle
  jitter      — the per-config synthesis-variation hashes of `synth`
                (string sha256, cheap relative to everything else)

`label_configs` adds the (B,) SSIM scores from the config-batched
functional model (`apps.accuracy_ssim_batch`) — the complete label row of
`core.dataset.build`. Parity with the scalar path is asserted in
tests/test_batch_oracle.py; docs/labeling.md is the operator's guide.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, NamedTuple, Sequence, Tuple

import networkx as nx
import numpy as np

from repro.accel import apps as apps_lib
from repro.accel import library as lib
from repro.accel import synth

EdgeGroup = Tuple[np.ndarray, np.ndarray]           # (src idx, dst idx)


@dataclass(frozen=True)
class CompiledApp:
    """Config-independent DAG precompilation for one accelerator."""
    node_ids: Tuple[str, ...]
    base_delay: np.ndarray        # (N,) float64: fixed latency + wire delay
    fixed_area: float
    fixed_power: float
    unit_pos: Tuple[int, ...]     # node index per app.unit_nodes entry
    jitter_order: Tuple[int, ...]  # unit_nodes indices sorted by node id
    fwd_groups: Tuple[EdgeGroup, ...]   # level-ascending, unique dst
    rev_groups: Tuple[EdgeGroup, ...]   # level-descending, unique src


def _conflict_free(edges: List[Tuple[int, int]], pos: int
                   ) -> List[EdgeGroup]:
    """Split edges into groups whose ``pos``-side endpoints are unique, so
    a fancy-indexed np.maximum assignment accumulates correctly."""
    groups: List[List[Tuple[int, int]]] = []
    used: List[set] = []
    for e in edges:
        for g, s in zip(groups, used):
            if e[pos] not in s:
                g.append(e)
                s.add(e[pos])
                break
        else:
            groups.append([e])
            used.append({e[pos]})
    return [(np.array([e[0] for e in g], np.int64),
             np.array([e[1] for e in g], np.int64)) for g in groups]


@functools.lru_cache(maxsize=None)
def compile_app(app_name: str) -> CompiledApp:
    app = apps_lib.APPS[app_name]
    acyclic = synth.acyclic_dataflow(app)
    ids = [n.id for n in app.nodes]
    idx = {nid: i for i, nid in enumerate(ids)}

    level = {nid: 0 for nid in ids}                 # longest-path depth
    for u in nx.topological_sort(acyclic):
        for _, v in acyclic.out_edges(u):
            level[v] = max(level[v], level[u] + 1)
    by_level: Dict[int, List[Tuple[int, int]]] = {}
    for u, v in acyclic.edges:
        by_level.setdefault(level[u], []).append((idx[u], idx[v]))

    fwd: List[EdgeGroup] = []
    rev: List[EdgeGroup] = []
    for lvl in sorted(by_level):
        fwd.extend(_conflict_free(by_level[lvl], pos=1))
    for lvl in sorted(by_level, reverse=True):
        rev.extend(_conflict_free(by_level[lvl], pos=0))

    base = np.zeros(len(ids), np.float64)
    fixed_area = fixed_power = 0.0
    for n in app.nodes:
        w = synth.wire_delay(acyclic, n.id)
        if n.fixed:
            pp = synth.FIXED_PPA[n.kind]
            base[idx[n.id]] = pp["latency"] + w
            fixed_area += pp["area"]
            fixed_power += pp["power"]
        else:
            base[idx[n.id]] = w                     # unit latency added later

    unit_pos = tuple(idx[n.id] for n in app.unit_nodes)
    jitter_order = tuple(sorted(range(len(app.unit_nodes)),
                                key=lambda j: app.unit_nodes[j].id))
    return CompiledApp(tuple(ids), base, fixed_area, fixed_power,
                       unit_pos, jitter_order, tuple(fwd), tuple(rev))


@functools.lru_cache(maxsize=None)
def _unit_tables(app_name: str, entries_items):
    """Per-unit-node float64 (area, power, latency) columns + entry names."""
    app = apps_lib.APPS[app_name]
    entries = dict(entries_items)
    area, power, lat, names = [], [], [], []
    for node in app.unit_nodes:
        ent = entries[node.kind]
        area.append(np.array([e.area for e in ent], np.float64))
        power.append(np.array([e.power for e in ent], np.float64))
        lat.append(np.array([e.latency for e in ent], np.float64))
        names.append(tuple(e.inst.name for e in ent))
    return tuple(area), tuple(power), tuple(lat), tuple(names)


def _jitter_cols(app: apps_lib.AccelDef, ca: CompiledApp, names,
                 C: np.ndarray) -> np.ndarray:
    """(B, 3) area/power/latency jitter factors — the per-config sha256
    hashes of `synth._jitter`, key-identical to the scalar oracle."""
    unit_ids = [n.id for n in app.unit_nodes]
    out = np.empty((C.shape[0], 3), np.float64)
    prefix = app.name + "|"
    for b in range(C.shape[0]):
        key = prefix + ",".join(
            f"{unit_ids[j]}:{names[j][C[b, j]]}" for j in ca.jitter_order)
        out[b] = (synth._jitter(key + "A"), synth._jitter(key + "P"),
                  synth._jitter(key + "L"))
    return out


def synthesize_batch(app: apps_lib.AccelDef, entries: Dict[str, Sequence],
                     configs) -> Dict[str, np.ndarray]:
    """Vectorized `synth.synthesize` over a (B, n_units) config block.

    Returns ``{area, power, latency: (B,), crit: (B, N) bool,
    node_delay: (B, N), node_ids}``; critical-node bit vectors are
    identical to the scalar oracle's sets, PPA within float tolerance.
    """
    ca = compile_app(app.name)
    C = np.asarray(configs, np.int64).reshape(-1, len(app.unit_nodes))
    B = C.shape[0]
    area_t, pow_t, lat_t, names = _unit_tables(
        app.name, apps_lib._entries_items(app, entries))

    area = np.full(B, ca.fixed_area)
    dyn = np.full(B, ca.fixed_power)
    delay = np.repeat(ca.base_delay[None, :], B, axis=0)
    for j, pos in enumerate(ca.unit_pos):
        cj = C[:, j]
        area += area_t[j][cj]
        dyn += pow_t[j][cj]
        delay[:, pos] += lat_t[j][cj]

    arrive = delay.copy()
    for src, dst in ca.fwd_groups:
        arrive[:, dst] = np.maximum(arrive[:, dst],
                                    arrive[:, src] + delay[:, dst])
    tmax = arrive.max(axis=1)

    # required-time back-propagation: a node is critical iff it sits on
    # some path achieving tmax (same 1e-9 tolerances as the scalar oracle)
    req = np.where(np.abs(arrive - tmax[:, None]) < 1e-9,
                   tmax[:, None], -1e30)
    for src, dst in ca.rev_groups:
        ok = (req[:, dst] > -1e29) & (
            np.abs(arrive[:, src] + delay[:, dst] - req[:, dst]) < 1e-9)
        cand = np.where(ok, arrive[:, src], -np.inf)
        req[:, src] = np.maximum(req[:, src], cand)

    jit = _jitter_cols(app, ca, names, C)
    return {"area": area * jit[:, 0],
            "power": dyn * (1 + synth.LEAKAGE_FRAC) * jit[:, 1],
            "latency": tmax * jit[:, 2],
            "crit": req > -1e29,
            "node_delay": delay,
            "node_ids": ca.node_ids}


@functools.lru_cache(maxsize=None)
def _unit_err_tables(app_name: str, entries_items):
    """Per-unit-node float64 (mae, wce) columns for error propagation."""
    app = apps_lib.APPS[app_name]
    entries = dict(entries_items)
    mae, wce = [], []
    for node in app.unit_nodes:
        ent = entries[node.kind]
        mae.append(np.array([e.mae for e in ent], np.float64))
        wce.append(np.array([e.wce for e in ent], np.float64))
    return tuple(mae), tuple(wce)


def timing_batch(app: apps_lib.AccelDef, entries: Dict[str, Sequence],
                 configs) -> Dict[str, np.ndarray]:
    """Timing-only slice of `synthesize_batch` for the DSE hot path.

    Vectorized `synth.static_timing` over a (B, n_units) config block:
    the arrival/required-time sweeps and the DAG error propagation, but
    NONE of the per-config sha256 jitter hashing (the Python loop that
    dominates `synthesize_batch` at large B), area/power sums, or SSIM
    labeling — cheap enough to run per surrogate featurization.

    Returns ``{slack, criticality, err_mae, err_wce: (B, N) float64,
    crit: (B, N) bool, tmax: (B,), node_ids}``; slack is normalized by
    tmax and criticality is arrive/tmax. slack/criticality/crit are
    exactly equal to the scalar reference (max/min sweeps over identical
    operands); err columns match to float tolerance (summation order).
    """
    ca = compile_app(app.name)
    C = np.asarray(configs, np.int64).reshape(-1, len(app.unit_nodes))
    B = C.shape[0]
    N = len(ca.node_ids)
    _, _, lat_t, _ = _unit_tables(
        app.name, apps_lib._entries_items(app, entries))
    mae_t, wce_t = _unit_err_tables(
        app.name, apps_lib._entries_items(app, entries))

    delay = np.repeat(ca.base_delay[None, :], B, axis=0)
    err_mae = np.zeros((B, N), np.float64)
    err_wce = np.zeros((B, N), np.float64)
    for j, pos in enumerate(ca.unit_pos):
        cj = C[:, j]
        delay[:, pos] += lat_t[j][cj]
        err_mae[:, pos] = mae_t[j][cj]
        err_wce[:, pos] = wce_t[j][cj]

    arrive = delay.copy()
    for src, dst in ca.fwd_groups:
        arrive[:, dst] = np.maximum(arrive[:, dst],
                                    arrive[:, src] + delay[:, dst])
        # each edge forwards its source's accumulated error mass exactly
        # once; level-ascending groups finalize sources before use
        err_mae[:, dst] += err_mae[:, src]
        err_wce[:, dst] += err_wce[:, src]
    tmax = arrive.max(axis=1)

    # crit bit: the same tolerance-based back-propagation as
    # `synthesize_batch` (bit-identical stage-1 labels)
    creq = np.where(np.abs(arrive - tmax[:, None]) < 1e-9,
                    tmax[:, None], -1e30)
    # slack: min-based required times — sinks carry tmax (all node delays
    # are positive, so the max arrival lands on a sink)
    is_sink = np.ones(N, bool)
    for src, _ in ca.fwd_groups:
        is_sink[src] = False
    req = np.where(is_sink[None, :], tmax[:, None], np.inf)
    for src, dst in ca.rev_groups:
        ok = (creq[:, dst] > -1e29) & (
            np.abs(arrive[:, src] + delay[:, dst] - creq[:, dst]) < 1e-9)
        cand = np.where(ok, arrive[:, src], -np.inf)
        creq[:, src] = np.maximum(creq[:, src], cand)
        req[:, src] = np.minimum(req[:, src], req[:, dst] - delay[:, dst])

    return {"slack": (req - arrive) / tmax[:, None],
            "criticality": arrive / tmax[:, None],
            "err_mae": err_mae, "err_wce": err_wce,
            "crit": creq > -1e29, "tmax": tmax, "node_ids": ca.node_ids}


class DeviceProbe(NamedTuple):
    """`probe_batch` left on the device, for a consumer that runs there
    (the GNN engine's forward): ``ssim`` holds one (B,) float32 device
    array per scale in `apps.PROBE_SIZES` (the probe distortion is
    ``1 - ssim``), and ``check()`` reads the LUT guards and raises
    `apps.LutDomainError` as `probe_batch` would."""
    ssim: Tuple[Any, ...]
    check: Callable[[], None]


class DeviceProber:
    """`probe_batch` without the read-back, for one accelerator and
    library: the same compiled programs on the same probe images
    (`apps.ssim_batch_on_device`), dispatched for every scale; nothing
    waits for the device. The labeler and the probe images are resolved
    once, at construction: per batch that lookup would cost more than
    the dispatch."""

    def __init__(self, app: apps_lib.AccelDef,
                 entries: Dict[str, Sequence], chunk: int = 1024):
        self._app, self._chunk = app, chunk
        self._labeler = apps_lib.batch_labeler(app, entries)
        self._image_sets = tuple(apps_lib.probe_inputs(app.name, size)
                                 for size in apps_lib.PROBE_SIZES)

    @functools.cached_property
    def lut_reads(self) -> int:
        """Truth-table entries the probe gathers per configuration, over
        every scale (`apps.Labeler.lut_reads`; 0 for an app without LUT
        units)."""
        return sum(self._labeler.lut_reads(images)
                   for images, _ in self._image_sets)

    def __call__(self, configs) -> DeviceProbe:
        C = np.asarray(configs, np.int64).reshape(
            -1, len(self._app.unit_nodes))
        return DeviceProbe(*apps_lib.ssim_batch_on_device(
            self._app, self._labeler, C, self._image_sets,
            chunk=self._chunk))


def probe_batch(app: apps_lib.AccelDef, entries: Dict[str, Sequence],
                configs, chunk: int = 1024) -> Dict[str, np.ndarray]:
    """Functional-probe distortion columns for a config block.

    Runs the config-batched functional model (`apps.accuracy_ssim_batch`'s
    compiled programs, through `DeviceProber`) on the tiny deterministic
    probe images (`apps.probe_inputs`, one per scale in
    `apps.PROBE_SIZES`), checks the LUT guards and returns ``{probe_err8,
    probe_err16: (B,) float64}`` where each value is 1 - SSIM vs the
    exact design. Graph-level features: `dataset.ConfigFeaturizer`
    broadcasts them across nodes. The compiled labeler is shared with
    dataset labeling (`_batch_label_fn` lru cache), so the probe adds one
    extra jit shape, not a second model."""
    probe = DeviceProber(app, entries, chunk)(configs)
    probe.check()
    return {f: 1.0 - np.asarray(s, np.float64)
            for f, s in zip(apps_lib.PROBE_FIELDS, probe.ssim)}


def crit_sets(rep: Dict[str, np.ndarray]) -> List[set]:
    """Per-config critical-node id sets (scalar-oracle format)."""
    ids = np.asarray(rep["node_ids"])
    return [set(ids[row]) for row in rep["crit"]]


def label_configs(app: apps_lib.AccelDef, entries: Dict[str, Sequence],
                  configs, images, exact_out=None, *, chunk: int = 256
                  ) -> Dict[str, np.ndarray]:
    """Complete batched label rows: synthesis PPA/critical bits + SSIM."""
    C = np.asarray(configs, np.int64).reshape(len(configs), -1)
    rep = synthesize_batch(app, entries, C)
    rep["ssim"] = apps_lib.accuracy_ssim_batch(
        app, entries, C, images, exact_out, chunk=chunk)
    return rep


def objective_rows(app: apps_lib.AccelDef, entries: Dict[str, Sequence],
                   configs, images, exact_out=None, *,
                   chunk: int = 256) -> np.ndarray:
    """(B, 4) minimization objectives [area, power, latency, 1-ssim] —
    the DSE-facing label layout, shared by the pipeline's oracle
    validation and `SurrogateEngine.from_oracle`."""
    rep = label_configs(app, entries, configs, images, exact_out,
                        chunk=chunk)
    return np.stack([rep["area"], rep["power"], rep["latency"],
                     1 - rep["ssim"]], axis=1).astype(np.float64)
