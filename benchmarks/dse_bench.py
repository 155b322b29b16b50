"""Search-layer benchmark: batched island fleet vs single-population DSE.

PR 1 made surrogate evaluation batched and memoized; this benchmark
measures (and GATES) the sampler layer that sits on top:

  * vectorized Pareto kernels — `non_dominated_sort` / `_niche_select`
    speedup over the reference Python-loop implementations
    (gate: vectorized niche select >= 1x the reference);
  * blockwise archive cull — `pareto_mask_blockwise` on a large random
    archive (gate: 1M rows in < 1s in full mode);
  * islands vs serial — merged-front hypervolume and wall-clock of the
    batched `repro.core.islands.run_islands` against single-population
    `nsga3` at equal evaluation budget on the Sobel design space under
    the critical-path-faithful `library_proxy_evaluator` (the evaluator
    is ~free, so wall-clock is dominated by the search itself). The
    scalar `run_islands_ref` fleet is timed too (full mode) so the
    batched-program speedup is visible.
    Gates: mean hv_ratio >= 1.0 AND islands wall-clock <= serial.

  * checkpoint overhead (``--checkpoint-every N`` > 0) — the crash-safe
    search path: `run_nsga`/`run_islands` emitting a per-generation/epoch
    `SearchCheckpoint` into a memory sink vs the plain run, interleaved
    alternating-order reps. Gates: results bit-identical (front AND a
    pickle-round-tripped mid-run kill/resume), pooled overhead <= 5%
    wall-clock. Written separately to BENCH_fault.json (CI's chaos
    smoke: ``--mode smoke --checkpoint-every 1``).

    PYTHONPATH=src python benchmarks/dse_bench.py [--mode smoke|full]
        [--budget 2048] [--seeds 0,1,2] [--out BENCH_dse.json]
        [--checkpoint-every 0] [--fault-out BENCH_fault.json]

Writes a JSON report (default BENCH_dse.json in the repo root) and prints
CSV-ish rows like benchmarks/run.py. ``--mode smoke`` is the CI
configuration: same gated search comparison, smaller kernel/cull sizes,
no informational extra fleets. Exits non-zero when any gate fails.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np


def pareto_kernel_bench(n: int = 512, n_obj: int = 4, reps: int = 3):
    """Vectorized-vs-reference timings for the Pareto hot path."""
    from repro.core import dse

    rng = np.random.default_rng(0)
    F = rng.random((n, n_obj))
    refs = dse.das_dennis(n_obj, 6)

    def best(fn):
        out, t = None, float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            t = min(t, time.perf_counter() - t0)
        return out, t

    fv, t_vec = best(lambda: dse.non_dominated_sort(F))
    fr, t_ref = best(lambda: dse.non_dominated_sort_ref(F))
    assert all(np.array_equal(a, b) for a, b in zip(fv, fr))
    front = F[fv[0]]
    need = max(1, len(front) // 2)
    sel_v, t_nvec = best(lambda: dse._niche_select(
        front, need, refs, np.random.default_rng(0)))
    sel_r, t_nref = best(lambda: dse._niche_select_ref(
        front, need, refs, np.random.default_rng(0)))
    assert np.array_equal(sel_v, sel_r)
    out = {"n": n, "n_obj": n_obj,
           "nds_ref_ms": round(t_ref * 1e3, 2),
           "nds_vec_ms": round(t_vec * 1e3, 2),
           "nds_speedup": round(t_ref / t_vec, 1),
           "niche_ref_ms": round(t_nref * 1e3, 2),
           "niche_vec_ms": round(t_nvec * 1e3, 2),
           "niche_speedup": round(t_nref / t_nvec, 1)}
    print(f"dse_bench,pareto_kernels,n={n},nds_speedup={out['nds_speedup']}x,"
          f"niche_speedup={out['niche_speedup']}x")
    return out


def blockwise_cull_bench(n_rows: int, n_obj: int = 4, gate_s: float = 1.0):
    """Time `pareto_mask_blockwise` on a random archive; parity-check the
    mask against the flat cull on a subsample."""
    from repro.core import dse

    rng = np.random.default_rng(2)
    F = rng.random((n_rows, n_obj))
    t0 = time.perf_counter()
    mask = dse.pareto_mask_blockwise(F)
    dt = time.perf_counter() - t0
    sub = rng.choice(n_rows, size=min(n_rows, 20_000), replace=False)
    assert np.array_equal(dse.pareto_mask_blockwise(F[sub], block=1024),
                          dse.pareto_mask(F[sub]))
    out = {"rows": n_rows, "n_obj": n_obj, "front": int(mask.sum()),
           "time_s": round(dt, 3), "gate_s": gate_s}
    print(f"dse_bench,blockwise_cull,rows={n_rows},front={out['front']},"
          f"time_s={dt:.3f}")
    return out


def _setup(app_name: str):
    from repro.accel import apps as apps_lib
    from repro.core import pruning
    from repro.core.islands import library_proxy_evaluator

    app = apps_lib.APPS[app_name]
    pruned, _ = pruning.prune_library()
    entries = {k: pruned[k] for k in {n.kind for n in app.unit_nodes}}
    sizes = [len(entries[n.kind]) for n in app.unit_nodes]
    return sizes, library_proxy_evaluator(app, entries)


def islands_vs_serial(app_name: str, budget: int, seeds, serial_pop: int,
                      pop: int, n_islands: int, epochs: int, migrate_k: int,
                      with_extras: bool = True):
    """One row per (seed, fleet): hv + wall-clock vs serial nsga3.

    The gated fleet is "nsga3-cones" — the batched homogeneous
    cone-partitioned NSGA-III fleet with merged-front elite broadcast
    (the `run_islands` defaults). `with_extras` adds informational rows:
    the scalar reference orchestrator at the same config (batched-program
    speedup) and the classic mixed fleet.
    """
    from repro.core import dse
    from repro.core.islands import (DEFAULT_SAMPLERS, run_islands,
                                    run_islands_ref)

    sizes, evaluate = _setup(app_name)
    fleets = [("nsga3-cones", run_islands, None)]
    if with_extras:
        fleets += [("nsga3-cones-ref", run_islands_ref, None),
                   ("mixed", run_islands, DEFAULT_SAMPLERS)]
    rows = []
    for seed in seeds:
        t0 = time.perf_counter()
        serial = dse.run_nsga(sizes, evaluate, budget, seed=seed,
                              pop=serial_pop)
        t_serial = time.perf_counter() - t0
        for fleet, runner, mix in fleets:
            t0 = time.perf_counter()
            isl = runner(sizes, evaluate, budget, seed=seed,
                         n_islands=n_islands, samplers=mix, pop=pop,
                         epochs=epochs, migrate_k=migrate_k)
            t_isl = time.perf_counter() - t0
            ref = dse.hv_reference(np.concatenate(
                [serial.pareto_objs, isl.pareto_objs], 0))
            hv_s = dse.hypervolume(serial.pareto_objs, ref,
                                   n_samples=16384)
            hv_i = dse.hypervolume(isl.pareto_objs, ref, n_samples=16384)
            row = {"seed": seed, "fleet": fleet, "budget": budget,
                   "serial": {"evaluated": serial.evaluated,
                              "front": len(serial.pareto_configs),
                              "hv": round(hv_s, 1),
                              "time_s": round(t_serial, 3)},
                   "islands": {"evaluated": isl.evaluated,
                               "front": len(isl.pareto_configs),
                               "hv": round(hv_i, 1),
                               "max_batch": isl.stats.get("max_batch"),
                               "time_s": round(t_isl, 3)},
                   "hv_ratio": round(hv_i / hv_s, 4)}
            rows.append(row)
            print(f"dse_bench,islands,seed={seed},fleet={fleet},"
                  f"hv_serial={hv_s:.4g},hv_islands={hv_i:.4g},"
                  f"ratio={row['hv_ratio']},"
                  f"time_serial={t_serial:.2f}s,time_islands={t_isl:.2f}s")
    return rows


def checkpoint_overhead_bench(app_name: str, budget: int, seed: int,
                              pop: int, every: int, reps: int = 7,
                              gate_pct: float = 5.0):
    """Crash-safe-search cost: checkpointed vs plain run, both samplers.

    The sink keeps the live checkpoint object (the serving path's
    memory-tier `ArtifactStore.put`), so the gated overhead is the
    search layer's own snapshot cost; disk-tier serialization is
    reported per row (``pickle_final_ms``/``ckpt_bytes``) but not
    gated. The evaluator is the ~free library proxy, so this is the
    worst case: search + checkpoint cost with nothing to hide behind.
    Correctness is asserted, not sampled: the checkpointed front must be
    bit-identical to the plain run's, and resuming from a
    pickle-round-tripped mid-run checkpoint (a simulated kill) must
    reproduce it too.
    """
    import gc
    import pickle

    from repro.core import dse
    from repro.core.islands import run_islands

    sizes, evaluate = _setup(app_name)

    def timed(fn):
        gc.collect()
        gc.disable()
        try:
            t0 = time.perf_counter()
            out = fn()
            return out, time.perf_counter() - t0
        finally:
            gc.enable()

    arms = [
        ("nsga3",
         lambda **kw: dse.run_nsga(sizes, evaluate, budget, seed=seed,
                                   pop=pop, **kw)),
        ("islands",
         lambda **kw: run_islands(sizes, evaluate, budget, seed=seed,
                                  n_islands=4, pop=max(2, pop // 4),
                                  epochs=4, migrate_k=4, **kw)),
    ]
    def measure_arm(sampler, run):
        # The sink keeps the live object, like the serving path's
        # memory-tier `ArtifactStore.put` — the gate isolates the
        # SEARCH-layer checkpoint cost (state snapshots every barrier).
        # Serialization cost is the store's business and is reported
        # (not gated) below as pickle_final_ms / ckpt_bytes.
        saved: list = []

        def sink(ck):
            saved.append(ck)

        def ckpt_run():
            saved.clear()
            return run(checkpoint_every=every, checkpoint_sink=sink)

        run()                             # untimed warmup (JIT, caches)
        # interleaved pairs, ALTERNATING order (flipping which arm goes
        # first each rep cancels the systematic position bias: the
        # second run of a pair tends to be slower). The overhead
        # estimate is min(ckpt) - min(plain): OS jitter is one-sided
        # additive noise, so per-arm minima converge on the true cost
        # while pairwise medians still carry several % of scatter on
        # sub-second arms.
        plain = ckpt = None
        t_plain = t_ckpt = float("inf")
        for rep in range(reps):
            order = [("plain", run), ("ckpt", ckpt_run)]
            if rep % 2:
                order.reverse()
            pair = {}
            for arm, fn in order:
                pair[arm] = timed(fn)
            plain_r, tp = pair["plain"]
            ckpt_r, tc = pair["ckpt"]
            if tp < t_plain:
                plain, t_plain = plain_r, tp
            if tc < t_ckpt:
                ckpt, t_ckpt = ckpt_r, tc
        same = (ckpt.pareto_configs == plain.pareto_configs
                and np.array_equal(ckpt.pareto_objs, plain.pareto_objs))
        # kill/resume: restart from a mid-run checkpoint on a fresh
        # engine — pickle round-tripped, like a crashed process would
        # reload it — and the front must still match bit for bit
        mid = pickle.loads(pickle.dumps(saved[len(saved) // 2]))
        res = run(resume_from=mid)
        resumed = (res.pareto_configs == plain.pareto_configs
                   and np.array_equal(res.pareto_objs, plain.pareto_objs))
        t0 = time.perf_counter()
        blob = pickle.dumps(saved[-1])    # disk-tier serialization cost
        t_pickle = time.perf_counter() - t0
        diff = max(0.0, t_ckpt - t_plain)
        overhead = diff / t_plain * 100.0
        row = {"_diff_s": diff,
               "sampler": sampler, "budget": budget, "seed": seed,
               "checkpoint_every": every, "reps": reps,
               "plain_s": round(t_plain, 3), "ckpt_s": round(t_ckpt, 3),
               "overhead_pct": round(overhead, 2),
               "n_checkpoints": len(saved),
               "ckpt_bytes": len(blob),
               "pickle_final_ms": round(t_pickle * 1e3, 3),
               "bit_identical": bool(same),
               "resume_bit_identical": bool(resumed)}
        print(f"dse_bench,checkpoint,sampler={sampler},"
              f"plain={t_plain:.3f}s,ckpt={t_ckpt:.3f}s,"
              f"overhead={overhead:.2f}%,n_ckpt={len(saved)},"
              f"identical={same},resume_identical={resumed}")
        return row

    # Gate on the POOLED overhead (both samplers' min-diffs over both
    # plain minima): a single sub-second arm cannot resolve 5% against
    # OS jitter, the pooled ~1s of search can. A sustained load window
    # (another process hogging the box for seconds) can still poison
    # every rep of one arm, so a pooled-gate miss RE-MEASURES — the
    # checkpoint cost is deterministic and a retry under quieter
    # conditions recovers it; only a persistent miss fails. Bit-identity
    # is checked on every attempt and never retried around.
    attempts = 0
    for attempt in range(3):
        attempts = attempt + 1
        rows = [measure_arm(sampler, run) for sampler, run in arms]
        pooled = max(0.0, 100.0 * sum(r["_diff_s"] for r in rows)
                     / sum(r["plain_s"] for r in rows))
        bad_bits = any(not r["bit_identical"]
                       or not r["resume_bit_identical"] for r in rows)
        if pooled <= gate_pct or bad_bits:
            break
        print(f"dse_bench,checkpoint,retry,pooled={pooled:.2f}%,"
              f"attempt={attempts}")
    for r in rows:
        r.pop("_diff_s")
    fails = [f"pooled checkpoint overhead {pooled:.2f}% > {gate_pct}% "
             f"({attempts} attempts)"] if pooled > gate_pct else []
    for r in rows:
        if not r["bit_identical"]:
            fails.append(f"{r['sampler']} checkpointed front != plain")
        if not r["resume_bit_identical"]:
            fails.append(f"{r['sampler']} resumed front != plain")
    return {"rows": rows,
            "gates": {"pooled_overhead_pct": round(pooled, 2),
                      "gate_pct": gate_pct, "attempts": attempts,
                      "ok": not fails}}, fails


def _fault_report(args, mode: str):
    """Run + persist the checkpoint-overhead section (BENCH_fault.json).

    The timing budget has a 4096-evaluation floor regardless of the
    search-comparison budget: resolving a 5% overhead gate needs enough
    wall-clock per arm to rise above OS scheduling jitter."""
    report, fails = checkpoint_overhead_bench(
        args.app, max(args.budget, 4096), seed=0, pop=args.serial_pop,
        every=args.checkpoint_every, gate_pct=args.ckpt_gate_pct)
    report = {"mode": mode, "app": args.app, **report}
    out = Path(args.fault_out)
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"dse_bench,fault_report,{out}")
    return fails


def fault_main() -> None:
    """Standalone entry for the checkpoint-overhead bench alone (the
    `fault` section of benchmarks/run.py)."""
    ap = argparse.ArgumentParser(
        description="crash-safe search checkpoint-overhead bench")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--app", default="sobel")
    ap.add_argument("--budget", type=int, default=2048)
    ap.add_argument("--serial-pop", type=int, default=32)
    ap.add_argument("--checkpoint-every", type=int, default=1)
    ap.add_argument("--ckpt-gate-pct", type=float, default=5.0)
    ap.add_argument("--fault-out", default="BENCH_fault.json")
    args = ap.parse_args()
    fails = _fault_report(args, "smoke" if args.smoke else "full")
    if fails:
        raise SystemExit("dse_bench GATE FAILURES: " + "; ".join(fails))
    print("dse_bench,fault_gates,ok")


def _apply_gates(report) -> list:
    """The CI/acceptance gates; returns a list of failure strings."""
    fails = []
    pk = report["pareto_kernels"]
    if pk["niche_speedup"] < 1.0:
        fails.append(f"niche_speedup {pk['niche_speedup']} < 1.0")
    bc = report["blockwise_cull"]
    if bc["time_s"] >= bc["gate_s"]:
        fails.append(f"blockwise cull {bc['time_s']}s >= {bc['gate_s']}s "
                     f"on {bc['rows']} rows")
    gated = [r for r in report["islands_vs_serial"]
             if r["fleet"] == "nsga3-cones"]
    mean_ratio = float(np.mean([r["hv_ratio"] for r in gated]))
    t_isl = sum(r["islands"]["time_s"] for r in gated)
    t_ser = sum(r["serial"]["time_s"] for r in gated)
    report["gates"] = {"mean_hv_ratio": round(mean_ratio, 4),
                       "islands_time_s": round(t_isl, 3),
                       "serial_time_s": round(t_ser, 3)}
    if mean_ratio < 1.0:
        fails.append(f"mean hv_ratio {mean_ratio:.4f} < 1.0")
    if t_isl > t_ser:
        fails.append(f"islands wall-clock {t_isl:.3f}s > serial "
                     f"{t_ser:.3f}s")
    return fails


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=("smoke", "full"), default="full",
                    help="smoke: CI gates with small kernel/cull sizes")
    ap.add_argument("--smoke", action="store_true",
                    help="alias for --mode smoke")
    ap.add_argument("--app", default="sobel")
    ap.add_argument("--budget", type=int, default=2048)
    ap.add_argument("--seeds", default="0,1,2")
    ap.add_argument("--serial-pop", type=int, default=32)
    ap.add_argument("--pop", type=int, default=8,
                    help="per-island population")
    ap.add_argument("--islands", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--migrate-k", type=int, default=4)
    ap.add_argument("--out", default="BENCH_dse.json")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="> 0: also run the checkpoint-overhead bench "
                         "(crash-safe search) and write --fault-out")
    ap.add_argument("--ckpt-gate-pct", type=float, default=5.0)
    ap.add_argument("--fault-out", default="BENCH_fault.json")
    args = ap.parse_args()

    from repro.core.artifacts import enable_compilation_cache
    enable_compilation_cache()
    mode = "smoke" if args.smoke else args.mode
    smoke = mode == "smoke"

    seeds = [int(s) for s in args.seeds.split(",") if s]
    report = {"mode": mode, "app": args.app,
              "pareto_kernels": pareto_kernel_bench(n=128 if smoke else 512),
              "blockwise_cull": blockwise_cull_bench(
                  n_rows=131_072 if smoke else 1_000_000, gate_s=1.0)}
    report["islands_vs_serial"] = islands_vs_serial(
        args.app, args.budget, seeds, args.serial_pop, args.pop,
        args.islands, args.epochs, args.migrate_k, with_extras=not smoke)
    by_fleet = {}
    for r in report["islands_vs_serial"]:
        by_fleet.setdefault(r["fleet"], []).append(r["hv_ratio"])
    report["mean_hv_ratio"] = {f: round(float(np.mean(v)), 4)
                               for f, v in by_fleet.items()}
    report["best_hv_ratio"] = {f: round(float(np.max(v)), 4)
                               for f, v in by_fleet.items()}
    print(f"dse_bench,summary,mean_hv_ratio={report['mean_hv_ratio']}")

    fails = _apply_gates(report)
    report["gates"]["ok"] = not fails
    if args.checkpoint_every > 0:
        fails += _fault_report(args, mode)

    out = Path(args.out)
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"dse_bench,report,{out}")
    if fails:
        raise SystemExit("dse_bench GATE FAILURES: " + "; ".join(fails))
    print("dse_bench,gates,ok")


if __name__ == "__main__":
    main()
