"""Labeling throughput: batched ground-truth engine vs per-config loop.

Dataset construction labels every sampled configuration with the
synthesis oracle (PPA + critical path) and the functional model (SSIM).
The scalar path pays a networkx DAG walk plus a full functional-model
re-trace per config; the batched path (`accel/batch_oracle.py` +
`apps.accuracy_ssim_batch`) labels (B, ...) blocks in one program:

    PYTHONPATH=src python benchmarks/dataset_bench.py [--smoke]
        [--apps sobel,gaussian] [--batches 256,1024] [--out BENCH_dataset.json]

Measures, per app,
  * loop_cps      — configs/sec through `synth.synthesize` +
                    `apps.accuracy_ssim`, one config at a time (timed on
                    a subsample — it is that slow);
  * batched_cps   — configs/sec through `batch_oracle.label_configs` at
                    each ``--batches`` size, steady state (one warm-up
                    call compiles the functional model);
  * a label-parity check on the first loop subsample.

Writes a JSON report (default BENCH_dataset.json) and fails if the
speedup at the largest batch is below the 20x acceptance floor on any
measured app. ``--smoke`` shrinks the loop subsample and app list for CI;
the headline batch stays 1024 so numbers are comparable across modes.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path

import numpy as np

SPEEDUP_FLOOR = 20.0


def sample_configs(app, entries, n: int, seed: int = 1) -> np.ndarray:
    rng = np.random.default_rng(seed)
    sizes = [len(entries[node.kind]) for node in app.unit_nodes]
    return np.stack([rng.integers(0, s, n) for s in sizes], axis=1)


def best_of(fn, reps: int = 2):
    """Min wall time over reps — damps scheduler noise on shared CPUs."""
    out, best = None, float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return out, best


def bench_app(app_name: str, batches, loop_n: int, n_images: int,
              img_size: int):
    import jax.numpy as jnp
    from repro.accel import apps as apps_lib
    from repro.accel import batch_oracle
    from repro.accel import library as lib
    from repro.accel import synth
    from repro.data import images as images_lib

    app = apps_lib.APPS[app_name]
    entries = {n.kind: lib.build_library(n.kind) for n in app.unit_nodes}
    imgs = images_lib.image_set(n_images, img_size)
    if app_name == "kmeans":
        inp = jnp.asarray(imgs.astype(np.int32))
    else:
        inp = jnp.asarray(images_lib.gray(imgs))
    exact_out = app.run(apps_lib.make_impls(app, apps_lib.exact_choice(app)),
                        inp)
    C = sample_configs(app, entries, max(batches))

    loop_n = min(loop_n, C.shape[0])    # can't time more configs than exist

    # -- per-config loop (the pre-batching labeling path) ------------------
    def loop_label(rows):
        out = []
        for row in rows:
            choice = {node.id: entries[node.kind][i]
                      for node, i in zip(app.unit_nodes, row)}
            rep = synth.synthesize(app, choice)
            acc = apps_lib.accuracy_ssim(app, choice, inp, exact_out)
            out.append([rep["area"], rep["power"], rep["latency"], acc])
        return np.asarray(out, np.float64)

    loop_label(C[:1])                               # warm the jnp dispatch
    loop_rows, loop_s = best_of(lambda: loop_label(C[:loop_n]))
    loop_cps = loop_n / loop_s
    print(f"dataset_bench,{app_name},loop,configs={loop_n},"
          f"time_s={loop_s:.2f},configs_per_sec={loop_cps:.1f}")

    # -- batched labeling engine ------------------------------------------
    chunk = min(256, max(batches))
    batch_oracle.label_configs(app, entries, C[:chunk], inp, exact_out,
                               chunk=chunk)         # compile the chunk shape
    batched = {}
    rep = None
    for B in sorted(batches):
        rep, t = best_of(lambda B=B: batch_oracle.label_configs(
            app, entries, C[:B], inp, exact_out, chunk=chunk))
        batched[B] = B / t
        print(f"dataset_bench,{app_name},batched,configs={B},"
              f"time_s={t:.3f},configs_per_sec={batched[B]:.1f}")

    # batched and loop labels must agree (same oracle, same model)
    got = np.stack([rep["area"][:loop_n], rep["power"][:loop_n],
                    rep["latency"][:loop_n], rep["ssim"][:loop_n]], 1)
    np.testing.assert_allclose(got[:, :3], loop_rows[:, :3], rtol=1e-9)
    np.testing.assert_allclose(got[:, 3], loop_rows[:, 3], atol=2e-5)

    top = max(batches)
    speedup = batched[top] / loop_cps
    print(f"dataset_bench,{app_name},summary,batch={top},"
          f"speedup={speedup:.1f}x")
    return {"loop_configs_per_sec": round(loop_cps, 1),
            "loop_sample": loop_n,
            "batched_configs_per_sec": {str(b): round(c, 1)
                                        for b, c in batched.items()},
            "speedup_at_max_batch": round(speedup, 1)}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="smaller loop subsample + app list for CI")
    ap.add_argument("--apps", default=None,
                    help="comma list (default: sobel,gaussian[,kmeans])")
    ap.add_argument("--batches", default="256,1024",
                    help="batch sizes (acceptance floor measured at max)")
    ap.add_argument("--loop-n", type=int, default=None,
                    help="configs timed through the per-config loop")
    ap.add_argument("--images", type=int, default=4)
    ap.add_argument("--img-size", type=int, default=64)
    ap.add_argument("--out", default="BENCH_dataset.json")
    args = ap.parse_args()

    from repro.core.artifacts import enable_compilation_cache
    enable_compilation_cache()

    apps = (args.apps.split(",") if args.apps
            else ["sobel", "gaussian"] if args.smoke
            else ["sobel", "gaussian", "kmeans"])
    batches = [int(b) for b in args.batches.split(",")]
    loop_n = args.loop_n or (16 if args.smoke else 48)

    t0 = time.time()
    report = {"mode": "smoke" if args.smoke else "full",
              "batches": batches,
              "images": [args.images, args.img_size],
              "apps": {}}
    for name in apps:
        report["apps"][name] = bench_app(name, batches, loop_n,
                                         args.images, args.img_size)
    report["total_s"] = round(time.time() - t0, 1)

    out = Path(args.out)
    out.write_text(json.dumps(report, indent=2) + "\n")
    worst = min(a["speedup_at_max_batch"] for a in report["apps"].values())
    print(f"dataset_bench,summary,worst_speedup={worst:.1f}x,report={out}")
    if worst < SPEEDUP_FLOOR:
        raise SystemExit(
            f"dataset_bench: batched labeling speedup {worst:.1f}x below "
            f"the {SPEEDUP_FLOOR:.0f}x acceptance floor")


if __name__ == "__main__":
    main()
