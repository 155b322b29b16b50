"""The readers of the program's own spans (`bench/spans.py` and the five
metrics that use it) on spans and device operations whose answers are
known, and the split of a recorded `.xplane.pb` into the calling thread
and its workers."""
import threading
from types import SimpleNamespace

import jax
import pytest

import cells  # noqa: F401  (puts the harness on the path)
import run
import spans

tr = run.load_module(run.BENCH / "trace.py", "bench_trace")

CALLING = [("engine.call", 10, 990, {}), ("engine.memo", 10, 60, {}),
           ("engine.wait_features", 100, 300, {}),
           ("engine.dispatch", 300, 310, {}),
           ("engine.wait_features", 400, 600, {}),
           ("engine.collect", 700, 800, {}),
           ("engine.assemble", 900, 950, {})]
WORKERS = [[("featurize.chunk", 90, 300, {}),
            ("featurize.timing", 100, 200, {}),
            ("featurize.probe", 200, 290, {})],
           [("featurize.chunk", 310, 600, {}),
            ("featurize.timing", 320, 450, {}),
            ("featurize.probe", 450, 1100, {})]]     # runs past the window
THREADS = spans.Threads((0, 1000), CALLING, WORKERS)
# TPU:0 is busy over [250, 350] and [500, 520]: the gap [350, 500] covers
# only [400, 500] of the second wait, the gap [0, 250] only [100, 250] of
# the first
DEVICES = {"/device:TPU:0": [("%a = f32[] add()", 250, 350),
                             ("%b = f32[] add()", 500, 520),
                             ("%late = f32[] add()", 1500, 1600)],
           "/device:TPU:1": [("%c = f32[] add()", 0, 1000)]}


@pytest.fixture
def read(monkeypatch):
    """A metric's reader on the given spans and device operations."""
    def read(metric, threads, devices=DEVICES):
        monkeypatch.setattr(spans, "threads", lambda cell: threads)
        ctx = SimpleNamespace(cell={"name": "sobel.wave"}, devices=devices,
                              trace=tr)
        return run.reader(metric).read(metric, ctx)
    return read


@pytest.mark.parametrize("metric,want", [
    ("wait_features_share.wave", 40.0),       # (200 + 200) / 1000
    ("memo_share.wave", 10.0),                # (50 + 50) / 1000
    ("timing_share.wave", 23.0),              # (100 + 130) / 1000
    ("probe_share.wave", 64.0),               # (90 + 550, clipped) / 1000
    # waits 400 ns, of which TPU:0 ran [250, 300] and [500, 520]
    ("idle_wait_share.wave", 33.0),
])
def test_each_reader_on_known_spans(read, metric, want):
    assert read(metric, THREADS) == pytest.approx(want)


@pytest.mark.parametrize("metric", [
    "wait_features_share.wave", "memo_share.wave", "timing_share.wave",
    "probe_share.wave", "idle_wait_share.wave"])
def test_readers_report_nothing_without_the_programs_spans(read, metric):
    # a program that opens no spans of its own, or no trace at all
    assert read(metric, spans.Threads((0, 1000), [], [])) is None
    assert read(metric, None) is None


def test_idle_wait_needs_a_device(read):
    assert read("idle_wait_share.wave", THREADS, devices={}) is None


def test_interval_overlap():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (45, 60)]
    assert spans.overlap(a, b) == 5 + 5 + 5
    assert spans.overlap(a, []) == 0


def test_split_finds_the_calling_thread_by_its_window_span():
    lines = [[("featurize.chunk", 5, 9, {})],
             [("bench.window", 0, 20, {}), ("bench.wave", 1, 19, {}),
              ("engine.call", 2, 18, {})],
             [("other", 3, 4, {})]]
    t = spans.split(lines)
    assert t.window == (0, 20)
    assert t.calling == [("engine.call", 2, 18, {})]
    assert t.workers == [[("featurize.chunk", 5, 9, {})]]
    assert spans.split(lines[:1]) is None


def test_a_recorded_trace_puts_each_span_on_its_thread(tmp_path,
                                                       monkeypatch):
    def worker():
        with jax.profiler.TraceAnnotation("featurize.chunk", call=1,
                                          chunk=0):
            jax.numpy.ones(8).block_until_ready()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path / "sobel.wave"),
                            profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            th = threading.Thread(target=worker)
            with jax.profiler.TraceAnnotation("engine.wait_features",
                                              call=1, chunk=0):
                th.start()
                th.join(timeout=60)
    assert not th.is_alive()
    monkeypatch.setattr(spans, "TRACES", tmp_path)
    t = spans.threads("sobel.wave")
    assert [sp[0] for sp in t.calling] == ["engine.wait_features"]
    (worker_line,) = t.workers
    (name, s, e, args), = worker_line
    assert name == "featurize.chunk" and args == {"call": 1, "chunk": 0}
    lo, hi = t.window
    assert lo <= t.calling[0][1] <= s < e <= t.calling[0][2] <= hi
    assert spans.threads("no.such_cell") is None


def test_the_traces_are_where_run_writes_them():
    assert spans.TRACES == run.TRACES
