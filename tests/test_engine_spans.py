"""The engine's spans and counters (`EngineStats.span`) in a profiler trace.

Each engine phase opens a `jax.profiler.TraceAnnotation` whose duration is
also added to an `EngineStats` timer. Recorded on the CPU, the trace must
show every span where the engine says it runs (``engine.*`` on the calling
thread inside ``engine.call``, ``featurize.*`` on the prefetch worker's
line), tie each worker span to its call and chunk, and agree with the
counters: a counter is the summed duration of its spans. Where the probe
reads truth tables (kmeans), ``engine.guards`` reads its guards once a chunk
inside ``engine.collect``, and the ``featurize.probe`` spans and
``EngineStats.lut_reads`` count the table entries gathered; sobel reads none.
"""
import jax
import numpy as np
import pytest

from repro.core.engine import SurrogateEngine
from test_engine_sharded import _configs, _fake_pipeline

CALLING = ("engine.call", "engine.memo", "engine.wait_features",
           "engine.dispatch", "engine.collect", "engine.assemble")
COUNTERS = {"wall_time_s": ("engine.call",),
            "memo_s": ("engine.memo", "engine.assemble"),
            "feature_wait_s": ("engine.wait_features",),
            "dispatch_s": ("engine.dispatch",),
            "collect_s": ("engine.collect",),
            "featurize_s": ("featurize.chunk",),
            "timing_s": ("featurize.timing",),
            "probe_s": ("featurize.probe",),
            "guard_s": ("engine.guards",)}


def _traced(tmp_path, fn):
    """Run `fn` under the profiler; the host lines of the trace, each a
    list of ``(name, start_ns, end_ns, args)`` of the program's spans."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        fn()
    path, = tmp_path.glob("**/*.xplane.pb")
    data = jax.profiler.ProfileData.from_file(str(path))
    lines = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            spans = [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                      dict(e.stats)) for e in line.events
                     if e.name.startswith(("engine.", "featurize."))]
            if spans:
                lines.append(spans)
    return lines


def _named(lines, *names):
    return [sp for line in lines for sp in line if sp[0] in names]


def _check_counters(stats, lines, before=None):
    """Each counter, less its value `before` the trace, is the summed
    duration of its spans, within 1 ms per span."""
    for counter, spans in COUNTERS.items():
        got = _named(lines, *spans)
        total = sum(e - s for _, s, e, _ in got) * 1e-9
        grew = getattr(stats, counter) - (before or {}).get(counter, 0.0)
        assert grew == pytest.approx(total, abs=1e-3 * max(1, len(got))), \
            counter


def _check_lines(lines, n_chunks, call):
    calling = [ln for ln in lines if any(sp[0] == "engine.call" for sp in ln)]
    assert len(calling) == 1
    (_, lo, hi, args), = _named(calling, "engine.call")
    assert args["call"] == call
    for name in CALLING[1:]:
        found = _named(calling, name)
        assert found, name
        assert all(lo <= s <= e <= hi and a["call"] == call
                   for _, s, e, a in found), name
    workers = [ln for ln in lines if ln is not calling[0]]
    assert workers and all(sp[0].startswith("featurize.")
                           for ln in workers for sp in ln)
    assert not _named(calling, "featurize.chunk")
    chunks = _named(workers, "featurize.chunk")
    assert sorted((a["call"], a["chunk"]) for *_, a in chunks) == \
        [(call, k) for k in range(n_chunks)]
    waits = _named(calling, "engine.wait_features")
    assert sorted(a["chunk"] for *_, a in waits) == list(range(n_chunks))
    return calling[0], workers


def test_fake_pipeline_spans_nest_and_match_counters(tmp_path):
    eng = SurrogateEngine(_fake_pipeline(prepare_sleep=0.005,
                                         collect_sleep=0.002), chunk_size=8)
    eng(_configs(8, seed=5))                   # call 1, before the trace
    before = {c: getattr(eng.stats, c) for c in COUNTERS}
    lines = _traced(tmp_path, lambda: eng(_configs(40)))
    _check_lines(lines, n_chunks=5, call=2)
    (_, _, _, args), = _named(lines, "engine.call")
    assert args["configs"] == 40 and args["misses"] == 40
    assert eng.stats.chunks == 6
    _check_counters(eng.stats, lines, before)


def test_single_chunk_call_opens_one_backend_span_and_waits_for_nothing(
        tmp_path):
    eng = SurrogateEngine(_fake_pipeline(prepare_sleep=0.005), chunk_size=64)
    lines = _traced(tmp_path, lambda: eng(_configs(10)))
    names = {sp[0] for ln in lines for sp in ln}
    assert {"engine.call", "engine.memo", "engine.backend",
            "engine.assemble"} <= names
    assert not names & {"engine.wait_features", "featurize.chunk"}
    assert eng.stats.feature_wait_s == 0.0
    assert eng.stats.featurize_s == 0.0
    _check_counters(eng.stats, lines)


def _gnn_engine(app_name):
    """A pipelined `from_gnn` engine over `app_name`'s pruned space, every
    chunk shape compiled and its stats reset, with 48 configurations it
    has not seen (three chunks of 16)."""
    from repro.accel import apps as apps_lib
    from repro.core import dataset as ds_lib, gnn, models, pruning

    pruned, _ = pruning.prune_library()
    app = apps_lib.APPS[app_name]
    entries = {k: pruned[k] for k in {n.kind for n in app.unit_nodes}}
    ds = ds_lib.build(app_name, n_samples=24, seed=0, lib_entries=entries)
    two_cfg = models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch="gsae", n_layers=2, hidden=16, feature_dim=ds.x.shape[-1]))
    params = models.init(jax.random.PRNGKey(0), two_cfg)
    eng = SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                   chunk_size=16, use_kernel="off")
    sizes = [len(entries[n.kind]) for n in app.unit_nodes]
    rng = np.random.default_rng(3)
    cfgs = [tuple(int(rng.integers(0, s)) for s in sizes) for _ in range(96)]
    eng(cfgs[48:])                             # compile every chunk shape
    eng.clear_cache()
    eng.reset_stats()
    return eng, cfgs[:48]


@pytest.fixture(scope="module")
def sobel_engine():
    return _gnn_engine("sobel")


@pytest.fixture(scope="module")
def kmeans_engine():
    return _gnn_engine("kmeans")


def test_gnn_engine_names_every_phase_on_its_thread(tmp_path, sobel_engine):
    eng, cfgs = sobel_engine
    lines = _traced(tmp_path, lambda: eng(cfgs))
    calling, workers = _check_lines(lines, n_chunks=3, call=1)
    for name in ("featurize.timing", "featurize.probe"):
        found = _named(workers, name)
        assert len(found) == 3, name
        chunks = _named(workers, "featurize.chunk")
        assert all(any(cs <= s <= e <= ce for _, cs, ce, _ in chunks)
                   for _, s, e, _ in found), name
    assert eng.stats.timing_s > 0 and eng.stats.probe_s > 0
    assert eng.stats.timing_s + eng.stats.probe_s <= eng.stats.featurize_s
    _check_counters(eng.stats, lines)


def _lut_reads_per_config(app_name):
    """Truth-table entries one configuration's probe reads, reckoned from
    the app's graph: each LUT-tabulated unit node (`library.LUT_DOMAINS`)
    is applied once per pixel of every probe image (one image per scale in
    `apps.PROBE_SIZES`)."""
    from repro.accel import apps as apps_lib
    from repro.accel import library as lib

    app = apps_lib.APPS[app_name]
    per_pixel = sum(n.kind in lib.LUT_DOMAINS for n in app.unit_nodes)
    return per_pixel * sum(size * size for size in apps_lib.PROBE_SIZES)


def test_kmeans_engine_reads_its_guards_once_a_chunk(tmp_path,
                                                     kmeans_engine):
    eng, cfgs = kmeans_engine
    lines = _traced(tmp_path, lambda: eng(cfgs))
    calling, workers = _check_lines(lines, n_chunks=3, call=1)
    guards = _named([calling], "engine.guards")
    assert sorted(a["chunk"] for *_, a in guards) == [0, 1, 2]
    collects = _named([calling], "engine.collect")
    for _, s, e, a in guards:
        assert a["call"] == 1
        assert any(cs <= s <= e <= ce and ca["chunk"] == a["chunk"]
                   for _, cs, ce, ca in collects)
    assert eng.stats.guard_s > 0
    assert eng.stats.guard_s <= eng.stats.collect_s
    _check_counters(eng.stats, lines)


def test_kmeans_lut_reads_are_reckoned_from_graph_probe_and_configs(
        tmp_path, kmeans_engine):
    eng, cfgs = kmeans_engine
    per_config = _lut_reads_per_config("kmeans")
    assert per_config == 8 * (8 * 8 + 16 * 16)     # 6 mul8 + 2 sqrt18
    eng.clear_cache()
    eng.reset_stats()
    lines = _traced(tmp_path, lambda: eng(cfgs))
    probes = _named(lines, "featurize.probe")
    assert [a["lut_reads"] for *_, a in probes] == [16 * per_config] * 3
    assert eng.stats.lut_reads == len(cfgs) * per_config
    assert eng.stats.as_dict()["lut_reads"] == len(cfgs) * per_config


def test_sobel_reads_no_table_and_no_guard(tmp_path, sobel_engine):
    eng, cfgs = sobel_engine
    assert _lut_reads_per_config("sobel") == 0
    eng.clear_cache()
    eng.reset_stats()
    lines = _traced(tmp_path, lambda: eng(cfgs))
    assert not _named(lines, "engine.guards")
    probes = _named(lines, "featurize.probe")
    assert len(probes) == 3
    assert all(a["lut_reads"] == 0 for *_, a in probes)
    assert eng.stats.lut_reads == 0 and eng.stats.guard_s == 0.0
