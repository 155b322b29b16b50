"""The readers of the LUT path (`guard_share`, `lut_gather_roofline`) on
spans and device operations whose answers are known, the gather matched by
the instruction text a TPU trace names it by."""
from types import SimpleNamespace

import pytest

import cells  # noqa: F401  (puts the harness on the path)
import run
import spans

# a TPU trace's gather, as XLA compiles `apps.lut_gather` for a v5e, and its
# neighbours: the clamp of its indices and the surrogate's kernel
GATHER = ("%fusion.10 = s32[131072]{0:T(1024)S(1)} fusion(s32[6029312]"
          "{0:T(1024)} %constant.96.clone.6, s32[131072]{0:T(1024)S(1)} "
          "%broadcast_clamp_fusion.5), kind=kCustom, "
          "calls=%fused_computation.6.clone")
SQRT_GATHER = ("%fusion.15 = s32[32768]{0:T(1024)S(1)} fusion(s32[6291456]"
               "{0:T(1024)S(1)} %custom-call.17, s32[32768]{0:T(1024)S(1)} "
               "%broadcast_clamp_fusion), kind=kCustom, "
               "calls=%fused_computation.1.clone")
CLAMP = ("%broadcast_clamp_fusion.5 = s32[131072]{0:T(1024)S(1)} "
         "fusion(s32[512,256]{1,0:T(8,128)} %bitcast.84), kind=kLoop, "
         "calls=%fused_computation.94")
KERNEL = ("%gnn_mp.13 = f32[512,32,300]{2,1,0:T(8,128)S(1)} custom-call("
          "f32[512,32,300]{2,1,0} %p), custom_call_target=\"gnn_mp\"")

CALLING = [("engine.call", 10, 990, {}),
           ("engine.collect", 700, 800, {}), ("engine.guards", 700, 720, {}),
           ("engine.collect", 800, 900, {}), ("engine.guards", 800, 810, {})]
WORKERS = [[("featurize.chunk", 90, 300, {}),
            ("featurize.probe", 100, 150, {"lut_reads": 1000})],
           [("featurize.chunk", 310, 600, {}),
            ("featurize.probe", 500, 550, {"lut_reads": 2000}),
            ("featurize.probe", 580, 590, {}),          # a read-back span
            ("featurize.probe", 1200, 1300, {"lut_reads": 5000})]]
THREADS = spans.Threads((0, 1000), CALLING, WORKERS)
# gathers busy 100 ns inside the window, and 100 of the 200 ns of one that
# runs past its end
DEVICES = {"/device:TPU:0": [(GATHER, 100, 150), (SQRT_GATHER, 160, 210),
                             (CLAMP, 90, 100), (KERNEL, 300, 600),
                             (GATHER, 900, 1100)]}


@pytest.fixture
def read(monkeypatch):
    def read(metric, threads, devices=DEVICES):
        monkeypatch.setattr(spans, "threads", lambda cell: threads)
        ctx = SimpleNamespace(cell={"name": "kmeans.wave"}, devices=devices,
                              peaks=lambda: {"hbm_bytes_per_s": 800e9})
        return run.reader(metric).read(metric, ctx)
    return read


def test_guard_share_is_the_guard_spans_over_the_window(read):
    assert read("guard_share.wave", THREADS) == pytest.approx(3.0)


def test_lut_gather_roofline_on_known_reads_and_gathers(read):
    # 3,000 entries x 8 bytes at 800 GB/s: 30 ns, over 200 ns of gathers
    assert read("lut_gather_roofline.wave", THREADS) == pytest.approx(15.0)


def test_the_gather_is_matched_by_its_trace_text():
    mod = run.reader("lut_gather_roofline.wave")
    assert mod.GATHER.match(GATHER) and mod.GATHER.match(SQRT_GATHER)
    assert not mod.GATHER.match(CLAMP) and not mod.GATHER.match(KERNEL)
    assert mod.gather_seconds(DEVICES, (0, 1000)) == pytest.approx(2e-7)


@pytest.mark.parametrize("metric", ["guard_share.wave",
                                    "lut_gather_roofline.wave"])
def test_readers_report_nothing_without_the_programs_spans(read, metric):
    # a program that opens none of these spans (sobel, or the parent of
    # this path's instrumentation), or no trace at all
    bare = spans.Threads((0, 1000), [("engine.call", 10, 990, {})],
                         [[("featurize.probe", 100, 150, {})]])
    assert read(metric, bare) is None
    assert read(metric, None) is None


def test_roofline_needs_the_gathers_on_the_device(read):
    assert read("lut_gather_roofline.wave", THREADS, devices={}) is None
    no_gather = {"/device:TPU:0": [(KERNEL, 300, 600)]}
    assert read("lut_gather_roofline.wave", THREADS, no_gather) is None
