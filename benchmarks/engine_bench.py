"""Throughput benchmark: batched `SurrogateEngine` vs naive per-config eval.

The DSE hot loop evaluates thousands to millions of configs through the
GNN surrogate; this benchmark quantifies what the engine subsystem buys
over the naive path the pipeline used before (per-config Python
featurization + one jit dispatch per config):

    PYTHONPATH=src python benchmarks/engine_bench.py [--mode smoke|full]
        [--batch 1024] [--devices N] [--out BENCH_engine.json]

Measures
  * naive_cps    — configs/sec evaluating one config per call through
                   `dataset.features_for_configs` + jit'd `models.predict`
                   (timed on a subsample, it is that slow);
  * batched_cps  — configs/sec through the engine on a cold cache at
                   ``--batch`` configs per call (featurize/compute
                   overlap on — the default pipelined path);
  * overlap      — the same engine with ``overlap=False`` (strictly
                   serial chunk loop) plus the ``overlap_fraction``
                   stat, isolating what the prefetch pipeline hides;
  * sharded      — an engine with ``devices=0`` (every local device)
                   against the single-device engine, rows checked
                   bit-identical (`np.array_equal`); ``--devices N``
                   forces N host devices via XLA_FLAGS *before* jax
                   loads, so CPU CI can exercise an 8-way drain;
  * cached_cps   — same batch replayed permuted (memo-cache serve rate);
  * ragged chunk accounting on a non-power-of-two batch;
  * dynamic-featurization overhead — the schema-v2 timing block runs a
    batched oracle sweep plus the tiny-image functional probe per cold
    batch (`ConfigFeaturizer.dynamic_raw`); the same engine with a
    ``dynamic=False`` featurizer is the static baseline. With overlap
    the sweep runs on a worker thread behind device compute, so the
    end-to-end gate tightens from <= 1.5x to <= 1.05x on full-mode
    >= 8-core hosts (the featurizer-only ratio is reported unguarded —
    the GNN forward pass dominates the hot path, which is exactly why
    the sweep is affordable).

Writes a JSON report (default BENCH_engine.json in the repo root) and
prints CSV-ish rows like benchmarks/run.py. ``--mode smoke`` (or the
legacy ``--smoke`` alias) shrinks dataset and training (CI uses it); the
measured batch size stays >= 1024 so the headline speedup is comparable
across modes. Speedup gates scale with the host: the sharded >= 1.5x
and overlap <= 1.05x gates apply in full mode on >= 8-core hosts where
the device axis can actually spread (train_bench precedent); smaller
hosts keep a no-catastrophic-regression floor plus the bit-identity
check, which is host-independent.
"""
from __future__ import annotations

import argparse
import json
import os
import time
from pathlib import Path

import numpy as np


def build_surrogate(n_samples: int, epochs: int, app_name: str = "sobel",
                    seed: int = 0):
    """Train a small two-stage GNN surrogate; returns everything the
    engine and the naive path need."""
    from repro.accel import apps as apps_lib
    from repro.core import dataset as ds_lib
    from repro.core import gnn, models, pruning, training

    pruned, _ = pruning.prune_library()
    app = apps_lib.APPS[app_name]
    entries = {k: pruned[k] for k in {n.kind for n in app.unit_nodes}}
    ds = ds_lib.build(app_name, n_samples=n_samples, seed=seed,
                      lib_entries=entries)
    tr, _ = ds.split(0.9)
    two_cfg = models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch="gsae", n_layers=3, hidden=64, feature_dim=ds.x.shape[-1]))
    params = training.fit_two_stage(
        two_cfg, tr, training.TrainConfig(epochs=epochs, seed=seed))
    return app, entries, ds, two_cfg, params


def naive_evaluator(two_cfg, params, ds, app, entries):
    """The pre-engine evaluation path: per-call Python featurization and a
    jit call whose shape follows the batch (so B=1 calls dominate)."""
    import jax
    import jax.numpy as jnp
    from repro.core import dataset as ds_lib
    from repro.core import models

    jit_predict = jax.jit(lambda a, x, m: models.predict(
        two_cfg, params, a, x, m)[0])

    def evaluate(configs):
        A, X, M = ds_lib.features_for_configs(ds, app, entries, configs)
        y = np.asarray(jit_predict(jnp.asarray(A), jnp.asarray(X),
                                   jnp.asarray(M)))
        y = ds.denorm_y(y)
        y[:, 3] = 1 - y[:, 3]
        return y

    return evaluate


def sample_configs(app, entries, n: int, seed: int = 1):
    rng = np.random.default_rng(seed)
    sizes = [len(entries[node.kind]) for node in app.unit_nodes]
    return [tuple(int(rng.integers(0, s)) for s in sizes) for _ in range(n)]


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--mode", choices=("smoke", "full"), default=None,
                    help="smoke = small dataset/training for CI")
    ap.add_argument("--smoke", action="store_true",
                    help="legacy alias for --mode smoke")
    ap.add_argument("--batch", type=int, default=1024,
                    help="engine batch size (acceptance floor: 1024)")
    ap.add_argument("--naive-n", type=int, default=48,
                    help="configs timed through the naive per-config path")
    ap.add_argument("--chunk", type=int, default=512,
                    help="engine chunk size")
    ap.add_argument("--devices", type=int, default=0,
                    help="force this many host platform devices via "
                         "XLA_FLAGS (0 = leave the host as-is); lets CPU "
                         "CI measure an 8-way sharded drain")
    ap.add_argument("--out", default="BENCH_engine.json")
    args = ap.parse_args()
    smoke = args.smoke or args.mode == "smoke"

    # Device forcing must land in the environment BEFORE anything imports
    # jax — which is why every repro import in this file sits inside a
    # function body below this line.
    if args.devices > 1:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.devices}")

    import jax

    from repro.core.artifacts import enable_compilation_cache
    from repro.core.engine import SurrogateEngine

    # persistent compilation cache: warm runs skip the recompilation that
    # dominates setup_s
    cache_dir = enable_compilation_cache()

    n_samples, epochs = (160, 6) if smoke else (600, 25)
    t0 = time.time()
    app, entries, ds, two_cfg, params = build_surrogate(n_samples, epochs)
    setup_s = time.time() - t0
    print(f"engine_bench,setup,n_samples={n_samples},epochs={epochs},"
          f"devices={len(jax.devices())},time_s={setup_s:.1f},"
          f"xla_cache={cache_dir}")

    configs = sample_configs(app, entries, args.batch)

    def best_of(fn, reps=3):
        """Min wall time over reps — damps scheduler noise on shared CPUs
        (a single slow run must not flip the speedup verdict)."""
        out, best = None, float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            best = min(best, time.perf_counter() - t0)
        return out, best

    # -- naive per-config path (warm up jit on B=1 first) ------------------
    naive = naive_evaluator(two_cfg, params, ds, app, entries)
    naive([configs[0]])
    n_naive = min(args.naive_n, len(configs))
    naive_rows, naive_s = best_of(lambda: np.concatenate(
        [naive([c]) for c in configs[:n_naive]], 0))
    naive_cps = n_naive / naive_s
    print(f"engine_bench,naive,configs={n_naive},time_s={naive_s:.2f},"
          f"configs_per_sec={naive_cps:.1f}")

    # -- batched engine, cold cache ---------------------------------------
    engine = SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                      chunk_size=args.chunk)
    engine(configs[:args.chunk])        # compile the full-chunk shape

    def batched_cold():
        engine.clear_cache()
        engine.reset_stats()
        return engine(configs)

    batched_rows, batched_s = best_of(batched_cold)
    batched_cps = len(configs) / batched_s
    cold = engine.stats.as_dict()
    print(f"engine_bench,batched,backend={engine.backend},"
          f"configs={len(configs)},time_s={batched_s:.2f},"
          f"configs_per_sec={batched_cps:.1f},chunks={cold['chunks']},"
          f"overlap_fraction={cold['overlap_fraction']:.2f}")

    # engine and naive path must agree (same model, same features)
    np.testing.assert_allclose(batched_rows[:n_naive], naive_rows,
                               rtol=1e-4, atol=1e-4)

    # -- overlap off: the strictly serial chunk loop -----------------------
    engine_serial = SurrogateEngine.from_gnn(two_cfg, params, ds, app,
                                             entries, chunk_size=args.chunk,
                                             overlap=False)
    engine_serial(configs[:args.chunk])    # shapes already cached

    def serial_cold():
        engine_serial.clear_cache()
        engine_serial.reset_stats()
        return engine_serial(configs)

    serial_rows, serial_s = best_of(serial_cold)
    serial_cps = len(configs) / serial_s
    assert np.array_equal(batched_rows, serial_rows), \
        "overlap pipeline changed engine rows"
    print(f"engine_bench,overlap,on_cps={batched_cps:.1f},"
          f"off_cps={serial_cps:.1f},"
          f"gain={batched_cps / serial_cps:.2f}x")

    # -- multi-device drain: chunks spread over every local device --------
    engine_sharded = SurrogateEngine.from_gnn(two_cfg, params, ds, app,
                                              entries, chunk_size=args.chunk,
                                              devices=0)
    # compile the chunk shape on every device (one chunk each)
    engine_sharded(configs[:args.chunk * engine_sharded.devices])

    def sharded_cold():
        engine_sharded.clear_cache()
        engine_sharded.reset_stats()
        return engine_sharded(configs)

    sharded_rows, sharded_s = best_of(sharded_cold)
    sharded_cps = len(configs) / sharded_s
    sharded_speedup = sharded_cps / batched_cps
    # acceptance: spreading chunks is invisible in values — bit-identical,
    # not merely allclose (each device runs the single-device program)
    sharded_identical = bool(np.array_equal(batched_rows, sharded_rows))
    print(f"engine_bench,sharded,devices={engine_sharded.devices},"
          f"configs_per_sec={sharded_cps:.1f},"
          f"speedup_vs_single={sharded_speedup:.2f}x,"
          f"bit_identical={sharded_identical}")

    # -- warm cache replay (permuted order) --------------------------------
    engine.reset_stats()
    perm = [configs[i] for i in
            np.random.default_rng(2).permutation(len(configs))]
    t0 = time.time()
    engine(perm)
    cached_s = time.time() - t0
    cached_cps = len(configs) / max(cached_s, 1e-9)
    warm = engine.stats.as_dict()
    print(f"engine_bench,cached,configs={len(configs)},"
          f"time_s={cached_s:.3f},configs_per_sec={cached_cps:.0f},"
          f"hit_rate={warm['cache_hit_rate']:.2f}")

    # -- dynamic-featurization overhead (schema-v2 timing block) -----------
    # Static baseline: identical engine, but its featurizer skips the
    # batched timing sweep (`dynamic=False` leaves the dynamic columns at
    # their constant base values). Pre-seeding the dataset copy's
    # featurizer cache makes `from_gnn` pick it up.
    import dataclasses as _dc

    from repro.core import dataset as ds_lib

    feat_dyn = ds_lib.featurizer_for(ds, app, entries)
    ds_static = _dc.replace(ds)
    feat_static = ds_lib.ConfigFeaturizer(ds.graph, app, entries,
                                          ds.x.shape[1], schema=ds.schema,
                                          dynamic=False)
    feat_static.set_norm(ds.x_mean, ds.x_std)
    ds_static._featurizers = {ds_lib._entries_sig(entries): feat_static}
    engine_static = SurrogateEngine.from_gnn(
        two_cfg, params, ds_static, app, entries, chunk_size=args.chunk)
    engine_static(configs[:args.chunk])    # compile

    def static_cold():
        engine_static.clear_cache()
        engine_static.reset_stats()
        return engine_static(configs)

    _, static_s = best_of(static_cold)
    static_cps = len(configs) / static_s
    dyn_overhead = static_cps / batched_cps    # >1 = dynamic is slower
    # featurizer-only ratio (no gate: featurization is a minor slice of
    # the hot path, so a large ratio here is fine if end-to-end holds)
    _, feat_dyn_s = best_of(lambda: feat_dyn.normalized(configs))
    _, feat_static_s = best_of(lambda: feat_static.normalized(configs))
    feat_ratio = feat_dyn_s / max(feat_static_s, 1e-9)
    print(f"engine_bench,dynamic_overhead,static_cps={static_cps:.1f},"
          f"dynamic_cps={batched_cps:.1f},overhead={dyn_overhead:.2f}x,"
          f"featurizer_only={feat_ratio:.1f}x")

    # -- ragged final chunk accounting -------------------------------------
    engine.clear_cache()
    engine.reset_stats()
    ragged = sample_configs(app, entries, args.chunk + args.chunk // 3,
                            seed=3)
    engine(ragged)
    rag = engine.stats.as_dict()
    print(f"engine_bench,ragged,configs={len(ragged)},"
          f"chunks={rag['chunks']},padded={rag['padded']}")

    speedup = batched_cps / naive_cps
    cpus = os.cpu_count() or 1
    report = {
        "mode": "smoke" if smoke else "full",
        "app": app.name,
        "backend": engine.backend,
        "batch": len(configs),
        "chunk_size": args.chunk,
        "host_cpus": cpus,
        "naive_configs_per_sec": round(naive_cps, 1),
        "batched_configs_per_sec": round(batched_cps, 1),
        "cached_configs_per_sec": round(cached_cps, 1),
        "speedup_batched_vs_naive": round(speedup, 1),
        "cache_hit_rate_on_replay": warm["cache_hit_rate"],
        "ragged": {"configs": len(ragged), "chunks": rag["chunks"],
                   "padded_rows": rag["padded"],
                   "padded_fraction": round(rag["padded_fraction"], 3)},
        "overlap": {
            "on_configs_per_sec": round(batched_cps, 1),
            "off_configs_per_sec": round(serial_cps, 1),
            "gain_vs_serial": round(batched_cps / serial_cps, 3),
            "overlap_fraction": round(cold["overlap_fraction"], 3)},
        "sharded": {
            "devices": engine_sharded.devices,
            "forced_devices": args.devices,
            "configs_per_sec": round(sharded_cps, 1),
            "speedup_vs_single_device": round(sharded_speedup, 2),
            "bit_identical_to_single_device": sharded_identical},
        "dynamic_featurization": {
            "schema_version": ds.schema_version,
            "static_configs_per_sec": round(static_cps, 1),
            "overhead_vs_static": round(dyn_overhead, 3),
            "featurizer_only_ratio": round(feat_ratio, 2)},
        "setup_s": round(setup_s, 1),
        "compilation_cache_dir": cache_dir,
    }
    out = Path(args.out)
    out.write_text(json.dumps(report, indent=2) + "\n")
    print(f"engine_bench,summary,speedup={speedup:.1f}x,"
          f"report={out}")
    if not sharded_identical:
        raise SystemExit(
            "engine_bench: sharded engine rows diverged from the "
            "single-device engine (must be bit-identical)")
    if speedup < 5.0:
        raise SystemExit(
            f"engine_bench: batched speedup {speedup:.1f}x below the 5x "
            f"acceptance floor")
    # Host-scaled perf gates (train_bench precedent): on a full-mode
    # >= 8-core host the sharded drain must pay for itself and overlap
    # must hide the dynamic sweep; smaller hosts (1-2 core CI runners,
    # forced devices time-slicing one core) keep honest floors — sharding
    # and threading must at least not be catastrophic there.
    full_gates = not smoke and cpus >= 8
    if full_gates and engine_sharded.devices >= 2:
        if sharded_speedup < 1.5:
            raise SystemExit(
                f"engine_bench: sharded drain {sharded_speedup:.2f}x vs "
                f"single device, below the 1.5x full-mode gate")
    elif sharded_speedup < 0.5:
        raise SystemExit(
            f"engine_bench: sharded drain {sharded_speedup:.2f}x vs "
            f"single device — catastrophic regression (floor 0.5x)")
    overhead_gate = 1.05 if full_gates else 1.5
    if dyn_overhead > overhead_gate:
        raise SystemExit(
            f"engine_bench: dynamic featurization costs "
            f"{dyn_overhead:.2f}x the static featurizer on the DSE hot "
            f"path (gate: <= {overhead_gate}x)")


if __name__ == "__main__":
    main()
