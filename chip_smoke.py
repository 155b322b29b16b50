"""Drive ApproxPilot's main path once on a TPU and check what comes out.

    python chip_smoke.py              # one chip: label -> train -> engine
                                      #   -> search -> validate -> serve
    python chip_smoke.py --chips 4    # the cross-chip paths only, each
                                      #   beside its one-device result

Everything runs in this one process (a chip belongs to one process at a
time), through the calls a user makes: the cached pipeline stages of
`repro.core.pipeline`, `SurrogateEngine`, the NSGA-III and island
samplers, `validate_pareto` and `EvalService`. The model is the one of
`PipelineConfig.paper_faithful("kmeans")` (gsae, hidden 300, 5 layers per
stage, search budget 20,000) with seeded random data; kmeans exercises
both LUT kinds (two-operand `mul8` and unary `sqrt18`). The cuts made to
fit the run's time are printed, each with its reason, before the phases.

Each phase checks its result against the repo's own reference (the
scalar oracle, the pure-JAX forward, the NumPy rank kernel, one-shot
calls) and raises on a mismatch. Lines before the last are informational
(seconds per phase, rates); none is a metric. The last line of standard
output is one JSON object, ``{"ok": true, "device": {...}}``. The script
exits non-zero without printing it when JAX finds no TPU, when the
package is not next to this file, or when any check fails.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import sys
import tempfile
import time
from contextlib import contextmanager
from pathlib import Path
from typing import Optional

import numpy as np

APP = "kmeans"
LABELS = 16_384       # paper: 105,000 labels for kmeans
EPOCHS = 3            # paper: 100 epochs
WAVE = 8192           # configs in the engine's fused wave
VALIDATE_K = 8        # front points checked against the oracle
SPOT = 3              # dataset rows checked against the scalar oracle
SERVE_REQUESTS = 8    # concurrent predict requests
SERVE_CONFIGS = 64    # configs per predict request
SERVE_DSE_BUDGET = 512
SERVE_DSE_POP = 32
RANK_FLEET = (4, 128, 4)   # islands x members x objectives, --chips 4


def log(msg: str) -> None:
    print(msg, flush=True)


@contextmanager
def phase(name: str, times: dict):
    t0 = time.perf_counter()
    log(f"phase,{name},start")
    yield
    times[name] = time.perf_counter() - t0
    log(f"phase,{name},ok,seconds={times[name]:.3f}")


def _check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def _device() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def _import_repro() -> None:
    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro" / "__init__.py").is_file():
        raise SystemExit(f"chip_smoke: no package at {src}/repro; run this "
                         f"script from a checkout of the repository")
    sys.path.insert(0, str(src))


def smoke_config(labels: int, epochs: int, artifact_dir: str, **over):
    """(paper config, the config this run uses): the paper's widths with
    the label count and epochs cut, plus any `over`rides."""
    from repro.core import pipeline as P
    paper = P.PipelineConfig.paper_faithful(APP)
    cfg = dataclasses.replace(paper, n_samples=labels, epochs=epochs,
                              artifact_dir=artifact_dir, **over)
    return paper, cfg


def _misses(store, stage: str) -> int:
    return store.stats.as_dict()["misses"].get(stage, 0)


def _hits(store, stage: str) -> int:
    return store.stats.as_dict()["hits"].get(stage, 0)


# --------------------------------------------------------------------------
# one-chip phases
# --------------------------------------------------------------------------

def phase_label(cfg, store):
    """Prune + label through the cached stages; spot-check rows against
    the scalar oracle (`synth.synthesize` + `apps.accuracy_ssim`)."""
    from repro.accel import apps, synth
    from repro.core import pipeline as P

    t0 = time.perf_counter()
    ctx = P.stage_prune(cfg, store)
    ds = P.stage_dataset(cfg, store, ctx)
    dt = time.perf_counter() - t0
    _check(_misses(store, "dataset") == 1 and _hits(store, "dataset") == 0,
           f"dataset stage was not a fresh store miss: {store.stats.as_dict()}")
    y = np.asarray(ds.y_raw)
    _check(y.shape == (cfg.n_samples, 4), f"label shape {y.shape}")
    _check(bool(np.isfinite(y).all()), "non-finite labels")
    for i in range(SPOT):
        choice = {node.id: ctx.entries[node.kind][c]
                  for node, c in zip(ctx.app.unit_nodes, ds.configs[i])}
        rep = synth.synthesize(ctx.app, choice)
        ssim = apps.accuracy_ssim(ctx.app, choice, ctx.inp, ctx.exact_out)
        want = np.asarray([rep["area"], rep["power"], rep["latency"]])
        np.testing.assert_allclose(y[i, :3], want, rtol=1e-6)
        _check(abs(float(y[i, 3]) - ssim) <= 2e-5,
               f"row {i}: batched ssim {y[i, 3]} vs scalar {ssim}")
    log(f"label,configs={len(y)},labels_per_s={len(y) / dt:.1f},"
        f"ssim_mean={float(y[:, 3].mean()):.6f}")
    return ctx, ds


def phase_train(cfg, store, ds, min_r2: Optional[float] = 0.0):
    """The `training.py` scan at the configured widths; a store miss.
    PPA predictions must beat the mean predictor (R^2 > ``min_r2``)."""
    from repro.core import pipeline as P

    art = P.stage_train(cfg, store, ds)
    _check(_misses(store, "train") == 1 and _hits(store, "train") == 0,
           f"train stage was not a fresh store miss: {store.stats.as_dict()}")
    for name, m in art.metrics.items():
        _check(all(np.isfinite(v) for v in m.values()),
               f"non-finite {name} metrics {m}")
        log(f"train,{name}," + ",".join(f"{k}={v:.4f}"
                                        for k, v in m.items()))
    for name in ("area", "power", "latency") if min_r2 is not None else ():
        _check(art.metrics[name]["r2"] > min_r2,
               f"{name} r2 {art.metrics[name]['r2']} <= {min_r2}")
    return art


def phase_engine(cfg, store, ctx, ds, art, wave: int,
                 expect_backend: str = "pallas"):
    """`stage_engine` on one fused wave, against a pure-JAX engine on the
    same params within `engine.KERNEL_PARITY_TOL` (normalized targets)."""
    from repro.core import dataset as ds_lib
    from repro.core import pipeline as P
    from repro.core.engine import KERNEL_PARITY_TOL, SurrogateEngine

    eng = P.stage_engine(cfg, store, ctx, ds, art)
    _check(eng.backend == expect_backend,
           f"engine backend {eng.backend!r}, expected {expect_backend!r}")
    configs = ds_lib.sample_configs(ctx.app, wave, seed=cfg.seed + 1,
                                    lib_entries=ctx.entries)
    t0 = time.perf_counter()
    rows = eng(configs)
    dt = time.perf_counter() - t0
    _check(rows.shape == (len(configs), 4), f"rows shape {rows.shape}")
    _check(bool(np.isfinite(rows).all()), "non-finite engine rows")

    ref = SurrogateEngine.from_gnn(art.two_cfg, art.params, ds, ctx.app,
                                   ctx.entries, chunk_size=cfg.eval_chunk,
                                   use_kernel="off")
    want = ref(configs)
    # the tolerance holds on normalized targets: scale it per column
    scale = np.asarray(ds.y_std, np.float64)
    err = np.abs(rows - want) / scale
    bound = KERNEL_PARITY_TOL * (1.0 + np.abs(want) / scale)
    _check(bool((err <= bound).all()),
           f"{eng.backend} rows vs pure JAX: max normalized diff "
           f"{err.max():.3g} over tolerance {KERNEL_PARITY_TOL}")
    log(f"engine,backend={eng.backend},wave={len(configs)},"
        f"configs_per_s={len(configs) / dt:.1f},"
        f"max_norm_diff_vs_jax={err.max():.3g}")
    return eng


def phase_search(cfg, store, ctx, engine):
    """NSGA-III through `stage_search` at the configured budget, then one
    island search with the JAX rank kernel."""
    from repro.core import dataset as ds_lib
    from repro.core import dse, islands
    from repro.core import pipeline as P

    res = P.stage_search(cfg, store, ctx, engine)
    _check(_misses(store, "search") == 1, "search stage was not a miss")
    _check(len(res.pareto_configs) > 0, "nsga3: empty front")
    _check(bool(np.isfinite(res.pareto_objs).all()), "nsga3: non-finite")
    _check(bool(dse.pareto_mask(res.pareto_objs).all()),
           "nsga3 front holds dominated points")
    log(f"search,nsga3,budget={cfg.dse_budget},front={len(res.pareto_configs)},"
        f"hv={res.history[-1]['hypervolume']:.6g}")

    sizes = [len(ctx.entries[n.kind]) for n in ctx.app.unit_nodes]
    t0 = time.perf_counter()
    isl = islands.run_islands(sizes, engine, cfg.dse_budget, seed=cfg.seed,
                              n_islands=cfg.dse_islands,
                              pop=max(2, cfg.dse_pop // cfg.dse_islands),
                              migrate_k=cfg.dse_migrate_k,
                              nds_backend="jax")
    dt = time.perf_counter() - t0
    _check(len(isl.pareto_configs) > 0, "islands: empty front")
    _check(bool(np.isfinite(isl.pareto_objs).all()), "islands: non-finite")
    _check(bool(dse.pareto_mask(isl.pareto_objs).all()),
           "islands front holds dominated points")
    # the rank kernel on the chip against the NumPy peel, on a fleet of
    # surrogate objective rows
    members = 2 * max(2, cfg.dse_pop // cfg.dse_islands)
    F = engine(ds_lib.sample_configs(ctx.app, cfg.dse_islands * members,
                                     seed=cfg.seed + 2,
                                     lib_entries=ctx.entries))
    stack = F.reshape(cfg.dse_islands, members, F.shape[1])
    _check(np.array_equal(islands.fleet_ranks(stack, "jax"),
                          islands.fleet_ranks(stack, "numpy")),
           "JAX fleet ranks differ from the NumPy reference")
    log(f"search,islands,nds_backend=jax,budget={cfg.dse_budget},"
        f"front={len(isl.pareto_configs)},seconds={dt:.3f}")
    return res


def phase_validate(cfg, store, k: int):
    """`run_staged` on the warm store (every stage a hit), then
    `validate_pareto` against the batched oracle."""
    from repro.core import pipeline as P

    result = P.run_staged(cfg, store)
    hits = result.metrics["store"]["hits"]
    _check(all(hits.get(s) == 1 for s in ("dataset", "train", "search"))
           and not result.metrics["store"]["misses"],
           f"run_staged on a warm store rebuilt a stage: "
           f"{result.metrics['store']}")
    v = P.validate_pareto(result, k=k, store=store)
    _check(np.isfinite(v["mean_rel_err"]), f"validate_pareto: {v}")
    _check(all(np.isfinite(e) for e in v["per_obj"].values()),
           f"validate_pareto: {v}")
    log("validate,k={},{}".format(
        min(k, len(result.pareto_configs)),
        ",".join(f"rel_err_{n}={e:.4g}" for n, e in v["per_obj"].items())))
    return result


def phase_serve(cfg, store, ctx, engine, n_requests: int, n_configs: int,
                dse_budget: int, dse_pop: int):
    """`EvalService.warm_start` on the same store serves `engine` itself;
    concurrent predicts and one dse request, each `np.array_equal` to
    one-shot calls on that engine."""
    from repro.core import dse
    from repro.core import dataset as ds_lib
    from repro.launch.serve import EvalService, ServeRequest

    sizes = [len(ctx.entries[n.kind]) for n in ctx.app.unit_nodes]
    batches = [ds_lib.sample_configs(ctx.app, n_configs, seed=100 + i,
                                     lib_entries=ctx.entries)
               for i in range(n_requests)]
    dse_kw = {"pop": dse_pop}
    with EvalService(store) as svc:
        name = svc.warm_start(cfg)
        rids = [svc.submit(ServeRequest("predict", name, configs=b))
                for b in batches]
        dse_rid = svc.submit(ServeRequest("dse", name, sampler="nsga3",
                                          budget=dse_budget, seed=1,
                                          dse_kwargs=dse_kw))
        resps = svc.results(rids + [dse_rid])
        occupancy = svc.stats()[name]["batch_occupancy"]
    for r in resps:
        _check(r.ok, f"request {r.rid} failed: {r.error}")
    for b, r in zip(batches, resps[:-1]):
        _check(np.array_equal(r.value, engine(b)),
               f"predict {r.rid} differs from a one-shot call")
    one = dse.SAMPLERS["nsga3"](sizes, engine, dse_budget, seed=1,
                                **dse_kw)
    got = resps[-1].value
    _check(list(got.pareto_configs) == list(one.pareto_configs)
           and np.array_equal(got.pareto_objs, one.pareto_objs),
           "served dse front differs from the one-shot search")
    lat = sorted(r.latency_s for r in resps)
    log(f"serve,requests={len(resps)},occupancy={occupancy},"
        f"p50_s={lat[len(lat) // 2]:.4f},max_s={lat[-1]:.4f}")


# --------------------------------------------------------------------------
# four-chip phases
# --------------------------------------------------------------------------

def _shard_devices(arr) -> str:
    return " ".join(f"{s.index[0].start or 0}:{s.device.id}"
                    for s in sorted(arr.addressable_shards,
                                    key=lambda s: s.index[0].start or 0))


def phase_sharded_engine(cfg, ctx, ds, wave: int, n_dev: int):
    """Engine ``devices=n_dev`` against ``devices=1`` on one fused wave at
    the configured widths (seeded random params): bit-identical rows, and
    successive chunks on distinct devices."""
    import jax

    from repro.core import dataset as ds_lib
    from repro.core import gnn, models
    from repro.core.engine import SurrogateEngine

    two_cfg = models.TwoStageConfig(
        gnn=gnn.GNNConfig(arch=cfg.gnn_arch, n_layers=cfg.n_layers,
                          hidden=cfg.hidden, feature_dim=ds.x.shape[-1]),
        use_critical_path=cfg.use_critical_path,
        schema_version=getattr(ds, "schema_version", 1))
    params = models.init(jax.random.PRNGKey(cfg.seed), two_cfg)
    configs = ds_lib.sample_configs(ctx.app, wave, seed=cfg.seed + 1,
                                    lib_entries=ctx.entries)

    def engine(devices):
        return SurrogateEngine.from_gnn(
            two_cfg, params, ds, ctx.app, ctx.entries,
            chunk_size=cfg.eval_chunk, use_kernel=cfg.use_kernel,
            devices=devices, cache=False)

    one, many = engine(1), engine(n_dev)
    _check(many.devices == n_dev, f"engine device count {many.devices}")
    t0 = time.perf_counter()
    rows_many = many(configs)
    dt_many = time.perf_counter() - t0
    t0 = time.perf_counter()
    rows_one = one(configs)
    dt_one = time.perf_counter() - t0
    pb = many._pipeline
    X = pb.prepare(configs[:cfg.eval_chunk])
    held = [[d.id for d in pb.dispatch(X).devices()] for _ in range(n_dev)]
    log(f"sharded_engine,chunk={cfg.eval_chunk},"
        f"devices_of_successive_chunks={held}")
    _check(sorted(sum(held, [])) == sorted(d.id
                                           for d in jax.devices()[:n_dev]),
           f"successive chunks on devices {held}")
    _check(bool(np.isfinite(rows_one).all()), "non-finite engine rows")
    diff = np.abs(rows_one - rows_many).max(1)
    _check(not diff.any(),
           f"devices={n_dev} rows differ from devices=1: "
           f"{int((diff > 0).sum())} rows, max |diff| {diff.max():.3g}")
    log(f"sharded_engine,backend={many.backend},wave={len(configs)},"
        f"bit_identical=true,seconds_1={dt_one:.3f},"
        f"seconds_{n_dev}={dt_many:.3f}")


def phase_sharded_ranks(n_dev: int, fleet=RANK_FLEET, seed: int = 0):
    """The island fleet's JAX rank kernel sharded over the island axis
    against one device and the NumPy peel: identical ranks."""
    import jax

    from repro.core import dse, islands
    from repro.distributed import meshes

    F = np.random.default_rng(seed).random(fleet)
    R = islands._dense_ranks(F)
    Rs = meshes.shard_leading_axis(R, len(R), axis_name="island",
                                   max_devices=n_dev)
    held = {s.device.id for s in Rs.addressable_shards}
    log(f"sharded_ranks,island:device={_shard_devices(Rs)}")
    _check(len(held) == n_dev, f"rank shards on devices {sorted(held)}")
    kern = islands._ranks_jit()
    many = np.asarray(kern(Rs))
    one = np.asarray(kern(jax.device_put(R, jax.devices()[0])))
    _check(np.array_equal(many, one),
           f"{n_dev}-device ranks differ from one device")
    _check(np.array_equal(one, dse.non_dominated_ranks_batched(F)),
           "JAX ranks differ from the NumPy peel")
    log(f"sharded_ranks,fleet={'x'.join(map(str, fleet))},"
        f"bit_identical=true,fronts={int(one.max()) + 1}")


# --------------------------------------------------------------------------
# entry point
# --------------------------------------------------------------------------

def run_one_chip(root: str, times: dict) -> None:
    from repro.core.artifacts import ArtifactStore

    paper, cfg = smoke_config(LABELS, EPOCHS, root)
    log(f"cut,n_samples,{paper.n_samples}->{cfg.n_samples},reason=labeling "
        f"and training at the paper's 105k labels would not fit the run's "
        f"1200 s limit")
    log(f"cut,epochs,{paper.epochs}->{cfg.epochs},reason=the run checks the "
        f"training path, not the surrogate's converged accuracy")
    log(f"config,app={cfg.app},arch={cfg.gnn_arch},hidden={cfg.hidden},"
        f"n_layers={cfg.n_layers},dse_budget={cfg.dse_budget},"
        f"dse_pop={cfg.dse_pop},eval_chunk={cfg.eval_chunk}")
    store = ArtifactStore(cfg.artifact_dir)
    with phase("label", times):
        ctx, ds = phase_label(cfg, store)
    with phase("train", times):
        art = phase_train(cfg, store, ds)
    with phase("engine", times):
        eng = phase_engine(cfg, store, ctx, ds, art, WAVE)
    with phase("search", times):
        phase_search(cfg, store, ctx, eng)
    with phase("validate", times):
        phase_validate(cfg, store, VALIDATE_K)
    with phase("serve", times):
        phase_serve(cfg, store, ctx, eng, SERVE_REQUESTS, SERVE_CONFIGS,
                    SERVE_DSE_BUDGET, SERVE_DSE_POP)


def run_four_chips(root: str, times: dict, n_dev: int) -> None:
    from repro.core import pipeline as P
    from repro.core.artifacts import ArtifactStore

    # only the featurizer's normalization comes from data here: the
    # compared surrogate is seeded and untrained, as in the 1-vs-N tests
    paper, cfg = smoke_config(512, 0, root)
    log(f"cut,n_samples,{paper.n_samples}->{cfg.n_samples},reason=the "
        f"dataset only normalizes features for the sharded engine")
    store = ArtifactStore(cfg.artifact_dir)
    ctx = P.stage_prune(cfg, store)
    ds = P.stage_dataset(cfg, store, ctx)
    with phase("sharded_engine", times):
        phase_sharded_engine(cfg, ctx, ds, WAVE, n_dev)
    with phase("sharded_ranks", times):
        phase_sharded_ranks(n_dev)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the cross-chip paths, each beside "
                         "its one-device result")
    args = ap.parse_args(argv)

    dev = _device()
    log(f"device,platform={dev['platform']},kind={dev['kind']},"
        f"count={dev['count']}")
    if dev["platform"] != "tpu":
        print("chip_smoke: JAX found no TPU; this script checks the chip "
              "path only", file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{dev['count']} device(s)", file=sys.stderr)
        return 2
    _import_repro()
    from repro.core.artifacts import enable_compilation_cache
    log(f"compile_cache,dir={enable_compilation_cache()}")

    times: dict = {}
    t0 = time.perf_counter()
    with tempfile.TemporaryDirectory(prefix="chip_smoke-") as root:
        if args.chips == 1:
            run_one_chip(root, times)
        else:
            run_four_chips(root, times, args.chips)
    log(f"total,seconds={time.perf_counter() - t0:.3f},phases="
        + ",".join(times))
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
