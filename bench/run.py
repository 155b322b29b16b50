"""Run one benchmark cell once, on the chip this process is started on.

    python3 bench/run.py --workload sobel.wave --seed 7 --seconds 20 --trace 0

The cell (``workloads`` in ``BENCHMARK.json`` at the checkout's root) names
a configuration (``bench/configs/<name>.json``) and a traffic mix
(``bench/traffic/<mix>.json``, whose ``kind`` names the loop that reads it,
``bench/loops/<kind>.py``). The run sets the cell up and warms every shape its
traffic uses (``setup_s``, from process start to the window), measures for
``--seconds``, reads the device's peak memory, then compares what the timed
path produced with the plain reference (``reference.py``) against the
cell's limits (``bench/limits/<cell>.json``). With ``--trace 1`` the window
runs under the profiler and the line carries the cell's per-layer metrics,
each read by ``bench/metrics/<metric name before the first dot>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (``breakdown`` when
traced) and, last, ``checks``: each compared number beside its limit, as
also printed on the last lines of standard error. Without a TPU, or with
fewer chips than the cell asks for, the run exits 2 and prints no result.
"""
from __future__ import annotations

import time

_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Dict, Optional  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".jax_cache"
TRACES = ROOT / "bench_out" / "trace"
sys.path.insert(0, str(BENCH))


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def resolve(spec: Dict, workload: str) -> Dict:
    """The cell's entry, configuration, traffic and limits, by name."""
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    return {"cell": cell,
            "config": load_json(ROOT / configs[cell["config"]]["file"]),
            "traffic": load_json(BENCH / "traffic" / f"{cell['traffic']}.json"),
            "limits": load_json(BENCH / "limits" / f"{workload}.json")}


def load_module(path: Path, name: str):
    mod_spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def loop(kind: str):
    """The loop class that drives a traffic kind: ``bench/loops/<kind>.py``
    defines ``Loop``."""
    return load_module(BENCH / "loops" / f"{kind}.py", f"bench_loop_{kind}").Loop


def reader(metric: str):
    """The per-layer reader module of a metric: its name up to the first
    dot names ``bench/metrics/<reader>.py``."""
    name = metric.split(".", 1)[0]
    return load_module(BENCH / "metrics" / f"{name}.py", f"bench_metric_{name}")


def device_info(chips: int, require_tpu: bool = True) -> Dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if require_tpu and info["platform"] != "tpu":
        raise NoChip(f"JAX found no TPU (platform {info['platform']!r})")
    if require_tpu and info["count"] < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{info['count']}")
    return info


def import_program():
    src = ROOT / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise FileNotFoundError(f"no program at {src}/repro")
    sys.path.insert(0, str(src))
    from repro.core import (artifacts, dataset, dse, engine, gnn, graph,
                            models, pipeline)
    artifacts.enable_compilation_cache()
    import jax
    # no eviction: the cache holds this checkout's programs only, and
    # evicting one would make a later run compile inside its set-up
    jax.config.update("jax_compilation_cache_max_size", -1)
    return SimpleNamespace(dataset=dataset, dse=dse, engine=engine, gnn=gnn,
                           graph=graph, models=models, pipeline=pipeline)


class CompileCounter:
    """Programs JAX compiled or loaded from its cache while `on` is set (the
    window should load none: every shape is warmed up in set-up)."""

    def __init__(self):
        import jax
        self.on, self.loaded, self.compiled = False, 0, 0
        jax.monitoring.register_event_listener(self._event)
        jax.monitoring.register_event_duration_secs_listener(self._duration)

    def _event(self, event, **_):
        if self.on and event == "/jax/compilation_cache/compile_requests_use_cache":
            self.loaded += 1

    def _duration(self, event, _secs, **_):
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.compiled += 1


def peaks(kind: str) -> Dict:
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no published peaks for device kind {kind!r} in "
                       f"bench/peaks.json")
    return table[kind]


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             require_tpu: bool = True, overrides: Optional[Dict] = None,
             log=print) -> Dict:
    """One run of one cell; returns the result line's object."""
    spec = load_json(ROOT / "BENCHMARK.json")
    r = resolve(spec, workload)
    for key, val in (overrides or {}).items():
        r[key].update(val)
    dev = device_info(int(r["cell"]["chips"]), require_tpu)
    program = import_program()
    import jax

    drv = loop(r["traffic"]["kind"])(r["config"], r["traffic"], seed,
                                     seconds, program)
    drv.setup()
    counter = CompileCounter()
    phases = getattr(getattr(drv, "s", None), "phases", None)
    if phases:
        log("setup " + " ".join(f"{k}={v:.3f}" for k, v in phases.items()),
            file=sys.stderr)
    if trace:
        out = TRACES / workload
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(str(out), profiler_options=opts):
            counter.on = True
            drv.window()
            counter.on = False
    else:
        counter.on = True
        drv.window()
        counter.on = False
    log(f"window programs_loaded={counter.loaded} "
        f"compiled={counter.compiled}", file=sys.stderr)
    setup_s = drv.t0 - _START
    stats = jax.devices()[0].memory_stats() or {}
    dev["memory_peak_bytes"] = int(stats.get("peak_bytes_in_use", 0))
    numbers, failed = drv.check()
    checks = {k: {"value": v, "limit": r["limits"][k]["limit"]}
              for k, v in numbers.items()}
    correct = failed == 0 and all(c["value"] <= c["limit"]
                                  for c in checks.values())
    metrics: Dict = {}
    result = {"correct": correct, "attempted": drv.attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if trace:
        tr = load_module(BENCH / "trace.py", "bench_trace")
        devices, spans = tr.load(tr.latest(str(TRACES / workload)))
        red = tr.reduce(devices, spans)
        dev["busy_s"], dev["window_s"] = red["busy_s"], red["window_s"]
        ctx = SimpleNamespace(cell=r["cell"], config=r["config"],
                              traffic=r["traffic"], counters=drv.counters,
                              e2e=drv.e2e(), devices=devices, spans=spans,
                              reduced=red, trace=tr,
                              peaks=lambda: peaks(dev["kind"]))
        for m in spec["per_layer"]:
            if workload not in m.get("workloads", [workload]):
                continue
            v = reader(m["name"]).read(m["name"], ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = red["breakdown"]
    else:
        values = dict(drv.e2e(), setup_s=setup_s)
        for m in spec["end_to_end"]:
            if workload in m.get("workloads", [workload]):
                metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
