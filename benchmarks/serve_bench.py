"""Serving-layer benchmark: cross-request batching vs serial handling.

`launch/serve.py`'s `EvalService` coalesces surrogate queries from all
in-flight requests into fused engine waves (the LM decode-batching idiom
applied to ApproxPilot's evaluation layer). This benchmark fires an
identical 8-client concurrent workload at the service in both modes and
GATES the three claims the serving layer makes:

  * **parity** — every response row in BOTH modes is bit-identical
    (`np.array_equal`) to a fresh one-shot `as_engine` evaluation of the
    same configs: batching must be invisible in values;
  * **coalescing** — with 8 concurrent clients the mean cross-request
    batch occupancy (``submits / drains``) exceeds 1 and the largest
    fused wave exceeds any single request;
  * **throughput** (full mode) — batched mode sustains >= 1.5x the
    serial-mode request throughput under a dispatch-cost-dominated
    backend (each backend call pays a fixed latency, the regime real
    jitted accelerator surrogates live in — a fused wave amortizes one
    dispatch across every coalesced request, exactly like LM decode
    batching amortizes one forward pass across sequences).

Full mode also reports (informationally) a GNN-tenant section: a
warm-started staged-pipeline surrogate served end-to-end, with parity
against the `run_staged` engine and request latency percentiles.

    PYTHONPATH=src python benchmarks/serve_bench.py [--mode smoke|full]
        [--clients 8] [--per-client 8] [--out BENCH_serve.json]

Writes a JSON report (default BENCH_serve.json) and prints CSV-ish rows
like benchmarks/run.py. ``--mode smoke`` is the CI configuration: same
parity + occupancy gates on a smaller workload, throughput informational
(CI machines have unpredictable thread scheduling; the 1.5x gate runs in
full mode). Exits non-zero when any gate fails.
"""
from __future__ import annotations

import argparse
import json
import threading
import time
from pathlib import Path

import numpy as np


def _space(app_name: str):
    from repro.accel import apps as apps_lib
    from repro.core import pruning
    from repro.core.islands import library_proxy_evaluator

    app = apps_lib.APPS[app_name]
    pruned, _ = pruning.prune_library()
    entries = {k: pruned[k] for k in {n.kind for n in app.unit_nodes}}
    sizes = [len(entries[n.kind]) for n in app.unit_nodes]
    return sizes, library_proxy_evaluator(app, entries)


def _workload(sizes, clients: int, per_client: int, n_cfg: int):
    """Distinct, seed-determined configs per (client, request) — identical
    across modes so serial and batched runs serve the same queries."""
    def cfgs(c, r):
        rng = np.random.default_rng(10_000 * c + r)
        return [tuple(int(rng.integers(0, s)) for s in sizes)
                for _ in range(n_cfg)]
    return {(c, r): cfgs(c, r)
            for c in range(clients) for r in range(per_client)}


def _run_mode(evaluate, sizes, work, *, coalesce: bool, clients: int,
              per_client: int, app: str = "bench"):
    """Serve the workload with `clients` concurrent threads; returns
    wall-clock, latency percentiles, per-request rows and engine stats."""
    from repro.launch.serve import EvalService, ServeRequest

    with EvalService(coalesce=coalesce, max_workers=clients) as svc:
        svc.register(app, evaluate, sizes)
        barrier = threading.Barrier(clients)
        rids = {}

        def client(c):
            barrier.wait()
            rids[c] = [svc.submit(ServeRequest("predict", app,
                                               configs=work[(c, r)]))
                       for r in range(per_client)]

        threads = [threading.Thread(target=client, args=(c,))
                   for c in range(clients)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        resps = {c: svc.results(r, timeout=300.0) for c, r in rids.items()}
        wall = time.perf_counter() - t0
        stats = svc.stats()[app]

    flat = [resp for rs in resps.values() for resp in rs]
    assert all(r.ok for r in flat), [r.error for r in flat if not r.ok]
    lat = np.sort([r.latency_s for r in flat])
    n_req = clients * per_client
    drains = max(1, stats["drains"]) if coalesce else stats["calls"]
    return {
        "mode": "batched" if coalesce else "serial",
        "requests": n_req,
        "wall_s": round(wall, 4),
        "throughput_rps": round(n_req / wall, 1),
        "p50_ms": round(float(lat[len(lat) // 2]) * 1e3, 2),
        "p99_ms": round(float(lat[min(len(lat) - 1,
                                      int(len(lat) * 0.99))]) * 1e3, 2),
        "occupancy": round(stats["submits"] / drains, 3)
        if coalesce else 1.0,
        "mean_batch_configs": round(stats["configs"] / drains, 1),
        "max_batch": stats["max_batch"],
        "rows": {f"{c}/{r}": resps[c][r].value
                 for c in range(clients) for r in range(per_client)},
    }


def serving_bench(app: str, clients: int, per_client: int, n_cfg: int,
                  dispatch_ms: float):
    """Serial vs batched on the dispatch-cost-dominated proxy backend,
    plus the bit-identity parity check against one-shot evaluation."""
    from repro.core.dse import as_engine

    sizes, proxy = _space(app)
    work = _workload(sizes, clients, per_client, n_cfg)

    def dispatching(configs):
        # fixed per-backend-call latency: the jit-dispatch/launch cost a
        # real accelerator surrogate pays per wave regardless of rows
        time.sleep(dispatch_ms / 1e3)
        return proxy(configs)

    serial = _run_mode(dispatching, sizes, work, coalesce=False,
                       clients=clients, per_client=per_client)
    batched = _run_mode(dispatching, sizes, work, coalesce=True,
                        clients=clients, per_client=per_client)

    reference = as_engine(proxy)           # fresh, never saw the service
    parity = all(
        np.array_equal(mode["rows"][f"{c}/{r}"],
                       np.asarray(reference(work[(c, r)])))
        for mode in (serial, batched)
        for c in range(clients) for r in range(per_client))
    for mode in (serial, batched):
        del mode["rows"]                    # keep the JSON report small

    speedup = round(batched["throughput_rps"] / serial["throughput_rps"], 2)
    out = {"clients": clients, "per_client": per_client,
           "configs_per_request": n_cfg, "dispatch_ms": dispatch_ms,
           "serial": serial, "batched": batched,
           "speedup": speedup, "parity_bit_identical": parity}
    for mode in (serial, batched):
        print(f"serve_bench,{mode['mode']},rps={mode['throughput_rps']},"
              f"p50_ms={mode['p50_ms']},p99_ms={mode['p99_ms']},"
              f"occupancy={mode['occupancy']},max_batch={mode['max_batch']}")
    print(f"serve_bench,summary,speedup={speedup}x,parity={parity}")
    return out


def gnn_tenant_bench(app: str, n_requests: int = 16):
    """Informational: serve a warm-started staged-pipeline GNN tenant and
    check parity against the one-shot `run_staged` engine (shared store
    => same memoized engine object => bit-identical)."""
    from repro.core import pipeline as P
    from repro.core.artifacts import ArtifactStore
    from repro.launch.serve import EvalService, ServeRequest

    cfg = P.PipelineConfig(app=app, n_samples=120, epochs=4,
                           dse_budget=100, hidden=32, n_layers=2,
                           dse_pop=16)
    store = ArtifactStore(None)
    t0 = time.perf_counter()
    res = P.run_staged(cfg, store)
    t_pipeline = time.perf_counter() - t0

    with EvalService(store) as svc:
        t0 = time.perf_counter()
        name = svc.warm_start(cfg)
        t_warm = time.perf_counter() - t0
        rids = [svc.submit(ServeRequest("predict", name,
                                        configs=res.pareto_configs))
                for _ in range(n_requests)]
        resps = svc.results(rids, timeout=300.0)
    assert all(r.ok for r in resps), [r.error for r in resps]
    expect = np.asarray(res.engine(res.pareto_configs))
    parity = all(np.array_equal(r.value, expect) for r in resps)
    lat = np.sort([r.latency_s for r in resps])
    eng_stats = res.engine.stats.as_dict()
    out = {"pipeline_s": round(t_pipeline, 2),
           "warm_start_s": round(t_warm, 3),
           "requests": n_requests,
           "p50_ms": round(float(lat[len(lat) // 2]) * 1e3, 2),
           "p99_ms": round(float(lat[-1]) * 1e3, 2),
           "engine_devices": eng_stats["devices"],
           "overlap_fraction": round(eng_stats["overlap_fraction"], 3),
           "parity_vs_run_staged": parity}
    print(f"serve_bench,gnn_tenant,warm_start_s={out['warm_start_s']},"
          f"devices={out['engine_devices']},"
          f"overlap_fraction={out['overlap_fraction']},"
          f"p50_ms={out['p50_ms']},parity={parity}")
    return out


def _apply_gates(report, *, smoke: bool) -> list:
    """CI/acceptance gates; returns failure strings."""
    fails = []
    sv = report["serving"]
    if not sv["parity_bit_identical"]:
        fails.append("service responses not bit-identical to one-shot")
    if sv["batched"]["occupancy"] <= 1.0:
        fails.append(f"occupancy {sv['batched']['occupancy']} <= 1 "
                     f"(no cross-request coalescing)")
    if sv["batched"]["max_batch"] <= report["serving"]["configs_per_request"]:
        fails.append(f"max_batch {sv['batched']['max_batch']} never "
                     f"exceeded a single request")
    if not smoke and sv["speedup"] < 1.5:
        fails.append(f"batched speedup {sv['speedup']}x < 1.5x")
    gnn = report.get("gnn_tenant")
    if gnn is not None and not gnn["parity_vs_run_staged"]:
        fails.append("GNN tenant responses != run_staged engine rows")
    report["gates"] = {"parity": sv["parity_bit_identical"],
                       "occupancy": sv["batched"]["occupancy"],
                       "speedup": sv["speedup"],
                       "speedup_gated": not smoke}
    return fails


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=("smoke", "full"), default="full",
                    help="smoke: CI gates (parity+occupancy) on a small "
                         "workload; full adds the 1.5x throughput gate "
                         "and the GNN tenant section")
    ap.add_argument("--smoke", action="store_true",
                    help="alias for --mode smoke")
    ap.add_argument("--app", default="sobel")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--per-client", type=int, default=8)
    ap.add_argument("--configs-per-request", type=int, default=16)
    ap.add_argument("--dispatch-ms", type=float, default=3.0)
    ap.add_argument("--out", default="BENCH_serve.json")
    args = ap.parse_args()

    from repro.core.artifacts import enable_compilation_cache
    enable_compilation_cache()
    mode = "smoke" if args.smoke else args.mode
    smoke = mode == "smoke"

    per_client = min(args.per_client, 4) if smoke else args.per_client
    report = {"mode": mode, "app": args.app,
              "serving": serving_bench(args.app, args.clients, per_client,
                                       args.configs_per_request,
                                       args.dispatch_ms)}
    if not smoke:
        report["gnn_tenant"] = gnn_tenant_bench(args.app)

    fails = _apply_gates(report, smoke=smoke)
    report["gates"]["ok"] = not fails

    out = Path(args.out)
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"serve_bench,report,{out}")
    if fails:
        raise SystemExit("serve_bench GATE FAILURES: " + "; ".join(fails))
    print("serve_bench,gates,ok")


if __name__ == "__main__":
    main()
