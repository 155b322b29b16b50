"""The reduction from a trace to busy time, op time and the breakdown
(`bench/trace.py`), on events whose answers are known, and the reading of
a recorded `.xplane.pb`."""
import re

import jax
import jax.numpy as jnp
import pytest

import cells  # noqa: F401  (puts the harness on the path)
import run

# loaded by path: the standard library has a module named `trace` too
tr = run.load_module(run.BENCH / "trace.py", "bench_trace")

KERNEL = "%gnn_mp.3 = f32[8,32,300]{2,1,0} custom-call(f32[8,32,32]{2,1,0} %a)"
FUSION = "%fusion.2 = f32[8,300]{1,0} fusion(f32[8,32,300]{2,1,0} %gnn_mp.3)"
SPANS = [("bench.window", 0, 100), ("bench.wave", 5, 50),
         ("bench.wave", 55, 95)]
DEVICES = {"/device:TPU:0": [(KERNEL, 10, 30), (FUSION, 20, 40),
                             ("%x = f32[] add(f32[] %a)", 60, 70),
                             ("%late = f32[] add(f32[] %a)", 150, 160)],
           "/device:TPU:1": [(KERNEL, 0, 100)]}


def test_busy_is_the_union_inside_the_window_averaged_over_devices():
    red = tr.reduce(DEVICES, SPANS)
    assert red["window_s"] == pytest.approx(100e-9)
    # device 0: [10, 40] and [60, 70] -> 40 ns; device 1: 100 ns
    assert red["devices"] == 2
    assert red["busy_s"] == pytest.approx((40e-9 + 100e-9) / 2)


def test_op_time_counts_only_the_kernels_own_ops():
    pat = re.compile(r"^%gnn_mp(\.\d+)? = ")
    # the fusion that reads %gnn_mp.3 is not the kernel
    assert tr.op_time({"/device:TPU:0": DEVICES["/device:TPU:0"]}, SPANS,
                      pat) == pytest.approx(20e-9)


def test_idle_gaps_are_named_by_the_innermost_open_bench_span():
    red = tr.reduce({"/device:TPU:0": DEVICES["/device:TPU:0"]}, SPANS)
    gaps = red["breakdown"]["idle_gaps"]
    # gaps of device 0: [70, 100] mid 85, [40, 60] mid 50, [0, 10] mid 5
    assert [g[0] for g in gaps] == ["bench.wave", "no bench span",
                                    "bench.wave"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 20e-9, 10e-9])
    ops = red["breakdown"]["device_ops"]
    assert ops[0][0] == "%gnn_mp.3 f32[8,32,300]{2,1,0} custom-call"


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError):
        tr.reduce(DEVICES, [("bench.wave", 0, 10)])


def test_load_reads_the_bench_spans_of_a_recorded_trace(tmp_path):
    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with jax.profiler.TraceAnnotation("bench.window"):
            with jax.profiler.TraceAnnotation("bench.wave"):
                f(x).block_until_ready()
    devices, spans = tr.load(tr.latest(str(tmp_path)))
    names = sorted(n for n, _, _ in spans)
    assert names == ["bench.wave", "bench.window"]
    lo, hi = tr.window_of(spans)
    (_, s, e), = [sp for sp in spans if sp[0] == "bench.wave"]
    assert lo <= s < e <= hi
    assert devices == {}         # the CPU has no TPU plane
