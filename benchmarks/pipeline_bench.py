"""Staged-pipeline benchmark: cold vs cached-resume, shared vs per-app.

The ISSUE-5 tentpole split the monolithic `pipeline.run()` into cached
stages over a content-addressed `ArtifactStore` and added the cross-app
unified surrogate. This benchmark quantifies both:

    PYTHONPATH=src python benchmarks/pipeline_bench.py [--smoke]
        [--out BENCH_pipeline.json]

Measures
  * cold_s          — first staged run against an empty on-disk store
                      (prune + dataset + train + engine + search);
  * resume_s        — the SAME config through a FRESH store on the same
                      root (a new process resuming a sweep): dataset,
                      train and search all come back as disk cache hits;
  * sweep_s         — a different ``dse_budget`` on the shared store:
                      only the search stage re-runs (the amortized-DSE
                      path the cache exists for);
  * per_app_fit_s   — N independent per-app surrogate fits (dataset
                      stages cached; the old cost of serving N apps);
  * unified_fit_s   — ONE `unified_surrogate` fit over the same N apps
                      off the same cached datasets.

Acceptance gates: the resumed run must actually HIT the dataset+train
cache (asserted on store counters, not wall clock) and be >= 5x faster
than the cold run (>= 2x in --smoke, where the cold run is small). In
full mode the unified surrogate's union test-split R² is gated too:
ssim >= 0.95 (the config-dynamic timing features of the schema-v2
refactor are what lifted it from 0.803) with the PPA targets held at
>= 0.98 — a feature change that trades PPA accuracy for SSIM fails
here. The cold run's Pareto points are oracle-checked
(`validate_pareto`) and the mean relative error is recorded in the
report. Writes BENCH_pipeline.json.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import tempfile
import time
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny config for CI")
    ap.add_argument("--out", default="BENCH_pipeline.json")
    args = ap.parse_args()

    from repro.core.artifacts import enable_compilation_cache
    enable_compilation_cache()

    from repro.core import pipeline as P
    from repro.core.artifacts import ArtifactStore

    n_samples, epochs, hidden, budget = ((100, 4, 32, 80) if args.smoke
                                         else (400, 20, 64, 600))
    apps = ["sobel", "dct8"] if args.smoke else ["sobel", "gaussian",
                                                 "dct8"]
    floor = 2.0 if args.smoke else 5.0
    root = tempfile.mkdtemp(prefix="approxpilot-bench-")
    try:
        cfg = P.PipelineConfig(app="sobel", n_samples=n_samples,
                               epochs=epochs, hidden=hidden, n_layers=2,
                               dse_budget=budget, dse_pop=16,
                               artifact_dir=root)

        t0 = time.perf_counter()
        r_cold = P.run(cfg)
        cold_s = time.perf_counter() - t0
        print(f"pipeline_bench,cold,time_s={cold_s:.2f}")

        # oracle-check the selected Pareto designs (surrogate gap)
        val = P.validate_pareto(r_cold)
        print(f"pipeline_bench,validate_pareto,"
              f"mean_rel_err={val['mean_rel_err']:.4f}")

        # fresh store over the same root = a new process resuming
        t0 = time.perf_counter()
        r_resume = P.run(cfg)
        resume_s = time.perf_counter() - t0
        hits = r_resume.metrics["store"]["hits"]
        print(f"pipeline_bench,resume,time_s={resume_s:.2f},hits={hits}")
        if hits.get("dataset") != 1 or hits.get("train") != 1:
            raise SystemExit(
                f"pipeline_bench: resume missed the dataset/train cache "
                f"(hits={hits})")
        if r_resume.pareto_configs != r_cold.pareto_configs:
            raise SystemExit("pipeline_bench: resume changed the Pareto "
                             "front")

        store = ArtifactStore(root)
        t0 = time.perf_counter()
        P.run_staged(dataclasses.replace(cfg, dse_budget=budget + 40),
                     store=store)
        sweep_s = time.perf_counter() - t0
        print(f"pipeline_bench,sweep,time_s={sweep_s:.2f},"
              f"hits={store.stats.as_dict()['hits']}")

        # ---- shared-surrogate vs per-app fits ---------------------------
        # fresh memory store with datasets prebuilt (untimed), so BOTH
        # sides time only the surrogate fitting they actually do
        fit_store = ArtifactStore(None)
        base = P.PipelineConfig(n_samples=n_samples, epochs=epochs,
                                hidden=hidden, n_layers=2)
        per_cfg, per_ds = {}, {}
        for a in apps:
            ca = dataclasses.replace(base, app=a)
            per_cfg[a] = ca
            per_ds[a] = P.stage_dataset(ca, fit_store,
                                        P.stage_prune(ca, fit_store))

        t0 = time.perf_counter()
        for a in apps:
            P.stage_train(per_cfg[a], fit_store, per_ds[a])
        per_app_fit_s = time.perf_counter() - t0
        print(f"pipeline_bench,per_app_fits,n={len(apps)},"
              f"time_s={per_app_fit_s:.2f}")

        u = P.unified_surrogate(apps, base, store=fit_store)
        unified_fit_s = u.timings["train"]
        print(f"pipeline_bench,unified_fit,n={len(apps)},"
              f"time_s={unified_fit_s:.2f}")

        speedup = cold_s / max(resume_s, 1e-9)
        report = {
            "mode": "smoke" if args.smoke else "full",
            "n_samples": n_samples, "epochs": epochs, "hidden": hidden,
            "dse_budget": budget, "apps": apps,
            "cold_s": round(cold_s, 2),
            "resume_s": round(resume_s, 2),
            "sweep_s": round(sweep_s, 2),
            "speedup_resume_vs_cold": round(speedup, 1),
            "per_app_fit_s": round(per_app_fit_s, 2),
            "unified_fit_s": round(unified_fit_s, 2),
            "unified_union_r2": {
                t: round(u.metrics[t]["r2"], 3)
                for t in ("area", "power", "latency", "ssim")},
            "validate_pareto": {
                "mean_rel_err": round(val["mean_rel_err"], 4),
                "per_obj": {k: round(v, 4)
                            for k, v in val.get("per_obj", {}).items()}},
            "resume_hits": hits,
        }
        Path(args.out).write_text(json.dumps(report, indent=2) + "\n")
        print(f"pipeline_bench,summary,speedup={speedup:.1f}x,"
              f"report={args.out}")
        if speedup < floor:
            raise SystemExit(
                f"pipeline_bench: cached-resume speedup {speedup:.1f}x "
                f"below the {floor}x acceptance floor")
        # surrogate-quality gates (full mode only: the smoke config is
        # deliberately too tiny to train a predictive model)
        if not args.smoke:
            r2 = report["unified_union_r2"]
            if r2["ssim"] < 0.95:
                raise SystemExit(
                    f"pipeline_bench: unified union SSIM R2 "
                    f"{r2['ssim']:.3f} below the 0.95 gate")
            low_ppa = {t: r2[t] for t in ("area", "power", "latency")
                       if r2[t] < 0.98}
            if low_ppa:
                raise SystemExit(
                    f"pipeline_bench: unified union PPA R2 below the "
                    f"0.98 gate: {low_ppa}")
    finally:
        shutil.rmtree(root, ignore_errors=True)


if __name__ == "__main__":
    main()
