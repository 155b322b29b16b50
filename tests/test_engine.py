"""SurrogateEngine: chunking/padding, memo cache, featurizer and
Pallas-kernel-path parity, DSE integration."""
import numpy as np
import pytest

from repro.core.engine import SurrogateEngine, _ConfigFeaturizer


# --------------------------------------------------------------------------
# core engine mechanics on a cheap deterministic backend
# --------------------------------------------------------------------------

def _toy_rows(configs):
    """Deterministic (n, 3) objective rows derived from the config key."""
    a = np.asarray(configs, np.float64)
    return np.stack([a.sum(1), (a * a).sum(1), a.max(1)], 1)


class CountingBackend:
    def __init__(self, allowed_sizes=None):
        self.calls = []
        self.allowed = allowed_sizes

    def __call__(self, configs):
        self.calls.append(len(configs))
        if self.allowed is not None:
            assert len(configs) in self.allowed, \
                f"unexpected chunk size {len(configs)}"
        return _toy_rows(configs)


def _rand_configs(n, dims=5, card=9, seed=0):
    rng = np.random.default_rng(seed)
    return [tuple(int(v) for v in rng.integers(0, card, dims))
            for _ in range(n)]


def test_results_match_backend_and_order():
    eng = SurrogateEngine(CountingBackend(), chunk_size=16)
    cfgs = _rand_configs(37)
    np.testing.assert_allclose(eng(cfgs), _toy_rows(cfgs))


def test_cache_hits_on_repeat_and_permutation():
    be = CountingBackend()
    eng = SurrogateEngine(be, chunk_size=64)
    cfgs = _rand_configs(50, seed=1)
    y1 = eng(cfgs)
    n_unique = len(set(cfgs))
    assert eng.stats.evaluated == n_unique
    assert sum(be.calls) == n_unique

    perm = np.random.default_rng(2).permutation(len(cfgs))
    y2 = eng([cfgs[i] for i in perm])
    np.testing.assert_allclose(y2, y1[perm])      # rows follow the order
    assert sum(be.calls) == n_unique              # zero new backend work
    assert eng.stats.cache_hits == len(cfgs) + len(cfgs) - n_unique
    assert eng.stats.cache_hit_rate > 0.4


def test_within_batch_dedup():
    be = CountingBackend()
    eng = SurrogateEngine(be, chunk_size=64)
    c = _rand_configs(1, seed=3)[0]
    y = eng([c] * 10)
    assert sum(be.calls) == 1
    np.testing.assert_allclose(y, np.repeat(_toy_rows([c]), 10, 0))


def test_cache_disabled_still_dedupes_within_batch():
    be = CountingBackend()
    eng = SurrogateEngine(be, chunk_size=64, cache=False)
    cfgs = _rand_configs(20, seed=4)
    eng(cfgs)
    eng(cfgs)
    assert eng.cache_size == 0
    assert sum(be.calls) == 2 * len(set(cfgs))    # no cross-call memory


def test_ragged_final_chunk_padded_to_bucket():
    # chunk 16 -> fixed shapes must be powers of two capped at 16
    be = CountingBackend(allowed_sizes={16, 8, 4, 2, 1})
    eng = SurrogateEngine(be, chunk_size=16, fixed_shape=True)
    cfgs = _rand_configs(37, seed=5)              # 16 + 16 + pad(5 -> 8)
    y = eng(cfgs)
    np.testing.assert_allclose(y, _toy_rows(cfgs))
    assert be.calls == [16, 16, 8]
    assert eng.stats.padded == 3
    assert eng.stats.chunks == 3


def test_ragged_without_fixed_shape_uses_exact_sizes():
    be = CountingBackend()
    eng = SurrogateEngine(be, chunk_size=16, fixed_shape=False)
    eng(_rand_configs(21, seed=6))
    assert be.calls == [16, 5]
    assert eng.stats.padded == 0


def test_backend_row_count_mismatch_raises():
    eng = SurrogateEngine(lambda cfgs: _toy_rows(cfgs)[:-1], chunk_size=8)
    with pytest.raises(ValueError):
        eng(_rand_configs(4, seed=7))


# --------------------------------------------------------------------------
# GNN path: featurizer parity, engine-vs-reference, kernel-vs-jax
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def small_surrogate():
    from repro.accel import apps as apps_lib
    from repro.core import dataset as ds_lib
    from repro.core import gnn, models, pruning, training

    pruned, _ = pruning.prune_library()
    app = apps_lib.APPS["sobel"]
    entries = {k: pruned[k] for k in {n.kind for n in app.unit_nodes}}
    ds = ds_lib.build("sobel", n_samples=60, lib_entries=entries)
    tr, _ = ds.split(0.9)
    two_cfg = models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch="gsae", n_layers=2, hidden=24, feature_dim=ds.x.shape[-1]))
    params = training.fit_two_stage(two_cfg, tr,
                                    training.TrainConfig(epochs=2))
    return app, entries, ds, two_cfg, params


def _app_configs(app, entries, n, seed=0):
    rng = np.random.default_rng(seed)
    sizes = [len(entries[node.kind]) for node in app.unit_nodes]
    return [tuple(int(rng.integers(0, s)) for s in sizes)
            for _ in range(n)]


def test_featurizer_matches_reference(small_surrogate):
    from repro.core import dataset as ds_lib
    app, entries, ds, _, _ = small_surrogate
    cfgs = _app_configs(app, entries, 23, seed=1)
    _, X_ref, _ = ds_lib.features_for_configs(ds, app, entries, cfgs)
    X = _ConfigFeaturizer(ds, app, entries)(cfgs)
    np.testing.assert_allclose(X, X_ref, atol=1e-6)


def test_gnn_engine_matches_unbatched_reference(small_surrogate):
    import jax
    import jax.numpy as jnp
    from repro.core import dataset as ds_lib
    from repro.core import models

    app, entries, ds, two_cfg, params = small_surrogate
    cfgs = _app_configs(app, entries, 19, seed=2)
    # reference: the pre-engine pipeline evaluation path
    jit_predict = jax.jit(lambda a, x, m: models.predict(
        two_cfg, params, a, x, m)[0])
    A, X, M = ds_lib.features_for_configs(ds, app, entries, cfgs)
    y_ref = np.asarray(jit_predict(jnp.asarray(A), jnp.asarray(X),
                                   jnp.asarray(M)))
    y_ref = ds.denorm_y(y_ref)
    y_ref[:, 3] = 1 - y_ref[:, 3]

    eng = SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                   chunk_size=8)   # forces ragged chunks
    np.testing.assert_allclose(eng(cfgs), y_ref, rtol=1e-4, atol=1e-4)
    assert eng.stats.chunks == 3                   # 8 + 8 + pad(3 -> 4)
    assert eng.stats.padded == 1


@pytest.mark.parametrize("arch", ["gsae", "gcn"])
def test_kernel_path_parity(small_surrogate, arch):
    """Pallas gnn_mp kernel path vs pure-JAX path, both architectures the
    kernel supports (interpret mode off-TPU)."""
    import jax
    from repro.core import gnn, models
    from repro.core.engine import (_make_jax_predict, _make_kernel_predict)
    import jax.numpy as jnp

    app, entries, ds, _, _ = small_surrogate
    two_cfg = models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch=arch, n_layers=2, hidden=16, feature_dim=ds.x.shape[-1]))
    params = models.init(jax.random.PRNGKey(3), two_cfg)
    feat = _ConfigFeaturizer(ds, app, entries)
    X = jnp.asarray(feat(_app_configs(app, entries, 8, seed=4)))
    y_jax = np.asarray(_make_jax_predict(two_cfg, params, feat.adj,
                                         feat.mask)(X))
    y_ker = np.asarray(_make_kernel_predict(two_cfg, params, feat.adj,
                                            feat.mask)(X))
    np.testing.assert_allclose(y_ker, y_jax, rtol=1e-4, atol=1e-4)


def test_from_gnn_kernel_engine_matches_jax_engine(small_surrogate):
    app, entries, ds, two_cfg, params = small_surrogate
    cfgs = _app_configs(app, entries, 12, seed=5)
    eng_jax = SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                       use_kernel="off")
    eng_ker = SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                       use_kernel="on")
    assert eng_jax.backend == "jax"
    assert eng_ker.backend == "pallas"     # parity probe must pass on CPU
    np.testing.assert_allclose(eng_ker(cfgs), eng_jax(cfgs),
                               rtol=1e-4, atol=1e-4)


def test_use_kernel_on_rejects_unsupported_arch(small_surrogate):
    import jax
    from repro.core import gnn, models

    app, entries, ds, _, _ = small_surrogate
    two_cfg = models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch="gat", n_layers=1, hidden=8, feature_dim=ds.x.shape[-1]))
    params = models.init(jax.random.PRNGKey(0), two_cfg)
    with pytest.raises(ValueError, match="gat"):
        SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                 use_kernel="on")
    # auto picks pure JAX for an arch the kernel does not implement
    eng = SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                   use_kernel="auto")
    assert eng.backend == "jax"


@pytest.fixture
def steered_to_tpu(monkeypatch):
    """The engine's TPU branch on this CPU host: `ops.on_tpu` says TPU,
    while the kernel itself still runs in interpret mode."""
    from repro.kernels import gnn_mp as mp_lib
    from repro.kernels import ops

    real = mp_lib.gnn_mp
    monkeypatch.setattr(ops, "on_tpu", lambda: True)
    monkeypatch.setattr(mp_lib, "gnn_mp",
                        lambda *a, interpret, **kw: real(*a, interpret=True,
                                                         **kw))


def test_auto_on_tpu_means_the_kernel(small_surrogate, steered_to_tpu):
    import jax
    from repro.core import gnn, models

    app, entries, ds, two_cfg, params = small_surrogate
    cfgs = _app_configs(app, entries, 12, seed=6)
    eng = SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                   use_kernel="auto")
    assert eng.backend == "pallas"
    ref = SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                   use_kernel="off")
    np.testing.assert_allclose(eng(cfgs), ref(cfgs), rtol=1e-4, atol=1e-4)
    gat = models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch="gat", n_layers=1, hidden=8, feature_dim=ds.x.shape[-1]))
    eng = SurrogateEngine.from_gnn(gat, models.init(jax.random.PRNGKey(0),
                                                    gat),
                                   ds, app, entries, use_kernel="auto")
    assert eng.backend == "jax"


@pytest.mark.parametrize("use_kernel", ["on", "auto"])
def test_kernel_parity_failure_raises(small_surrogate, steered_to_tpu,
                                      monkeypatch, use_kernel):
    """A kernel that misses the parity tolerance stops construction: no
    mode falls back to pure JAX."""
    from repro.core import engine as engine_lib

    app, entries, ds, two_cfg, params = small_surrogate
    monkeypatch.setattr(engine_lib, "KERNEL_PARITY_TOL", -1.0)
    with pytest.raises(RuntimeError, match="parity"):
        SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                 use_kernel=use_kernel)


def test_kernel_build_error_propagates(small_surrogate, steered_to_tpu,
                                       monkeypatch):
    from repro.core import engine as engine_lib

    def broken(*a, **kw):
        raise NotImplementedError("kernel does not lower")

    app, entries, ds, two_cfg, params = small_surrogate
    monkeypatch.setattr(engine_lib, "_make_kernel_predict", broken)
    with pytest.raises(NotImplementedError, match="lower"):
        SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                 use_kernel="auto")


def test_rforest_engine_matches_flat_features(small_surrogate):
    from repro.core.rforest import RandomForest

    app, entries, ds, _, _ = small_surrogate
    tr, _ = ds.split(0.9)
    Xf = tr.flat_features()
    rf_models = {i: RandomForest(n_trees=4, seed=i).fit(Xf, tr.y[:, i])
                 for i in range(4)}
    eng = SurrogateEngine.from_rforest(rf_models, ds, app, entries)
    # engine featurization of a training config must reproduce the training
    # flat feature row (masked padding included)
    y = eng([tr.configs[0]])
    row = Xf[0:1]
    want = np.stack([rf_models[i].predict(row) * ds.y_std[i] + ds.y_mean[i]
                     for i in range(4)], 1)
    want[:, 3] = 1 - want[:, 3]
    np.testing.assert_allclose(y, want, rtol=1e-5, atol=1e-5)


# --------------------------------------------------------------------------
# DSE integration
# --------------------------------------------------------------------------

def test_samplers_report_engine_stats():
    from repro.core import dse

    def toy(configs):
        a = np.asarray(configs, np.float64)
        return np.stack([a.sum(1), 9 * 4 - a.sum(1) + a.std(1)], 1)

    res = dse.run_nsga([10] * 4, toy, 300, seed=0, pop=32)
    assert res.stats is not None
    assert res.stats["configs"] >= 300
    # NSGA re-visits parents/offspring constantly: the cache must fire
    assert res.stats["cache_hits"] > 0
    assert res.stats["evaluated"] <= res.stats["configs"]


def test_sampler_results_unchanged_by_engine_wrapping():
    """Memoization must not alter values, only cost."""
    from repro.core import dse

    def toy(configs):
        a = np.asarray(configs, np.float64)
        return np.stack([a.sum(1), a.std(1)], 1)

    r1 = dse.run_nsga([8] * 5, toy, 240, seed=3, pop=24)
    r2 = dse.run_nsga([8] * 5, dse.as_engine(toy), 240, seed=3, pop=24)
    np.testing.assert_allclose(r1.pareto_objs, r2.pareto_objs)
    assert r1.pareto_configs == r2.pareto_configs


# --------------------------------------------------------------------------
# concurrency: exact stats + the submit/drain cross-request queue
# --------------------------------------------------------------------------

def test_engine_stats_exact_under_8x1000_threads():
    """Regression for the stats mutation race: 8 threads x 1000 queries
    must land every counter on its exact total. The old bare
    ``stats.calls += 1`` read-modify-write lost increments under
    contention (non-atomic even with the GIL); `EngineStats.update` now
    holds a lock."""
    import threading

    from repro.core.engine import EngineStats

    n_threads, per_thread = 8, 1000
    stats = EngineStats()
    barrier = threading.Barrier(n_threads)

    def worker(t):
        barrier.wait()
        for i in range(per_thread):
            stats.update(calls=1, configs=3, cache_hits=1, evaluated=2)
            stats.bump_max(max_batch=t * per_thread + i)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    total = n_threads * per_thread
    assert stats.calls == total
    assert stats.configs == 3 * total
    assert stats.cache_hits == total
    assert stats.evaluated == 2 * total
    assert stats.max_batch == total - 1      # max over t*1000+i
    d = stats.as_dict()
    assert d["calls"] == total and d["configs"] == 3 * total


def test_concurrent_engine_queries_exact_totals():
    """8 threads querying ONE engine: results correct per-thread and the
    shared counters sum exactly (no lost updates end-to-end)."""
    import threading

    be = CountingBackend()
    eng = SurrogateEngine(be, chunk_size=64)
    n_threads, per_thread, width = 8, 125, 4
    work = {t: [_rand_configs(width, seed=1000 * t + i)
                for i in range(per_thread)] for t in range(n_threads)}
    barrier = threading.Barrier(n_threads)
    errs = []

    def worker(t):
        try:
            barrier.wait()
            for cfgs in work[t]:
                np.testing.assert_allclose(eng(cfgs), _toy_rows(cfgs))
        except BaseException as e:             # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=worker, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs, errs[0]
    assert eng.stats.calls == n_threads * per_thread
    assert eng.stats.configs == n_threads * per_thread * width
    # every row that was not a memo/batch-dedup hit reached the backend
    assert eng.stats.evaluated == sum(be.calls)
    assert eng.stats.cache_hits + eng.stats.evaluated == eng.stats.configs


def test_submit_drain_queue_parity_and_occupancy():
    """Producer threads submitting through `queued_view` while one
    batcher drains: every producer gets exactly the rows the backend
    computes for ITS configs, and queued submissions fuse (occupancy
    accounting: submits counted, drains <= submits)."""
    import threading

    eng = SurrogateEngine(CountingBackend(), chunk_size=256)
    stop = threading.Event()

    def batch_loop():
        while not stop.is_set():
            eng.drain(timeout=0.005)
        eng.drain(timeout=None)

    batcher = threading.Thread(target=batch_loop, daemon=True)
    batcher.start()
    n_threads, per_thread = 8, 20
    errs = []
    barrier = threading.Barrier(n_threads)

    def producer(t):
        view = eng.queued_view()
        try:
            barrier.wait()
            for i in range(per_thread):
                cfgs = _rand_configs(6, seed=31 * t + i)
                np.testing.assert_allclose(view(cfgs), _toy_rows(cfgs))
        except BaseException as e:             # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=producer, args=(t,))
               for t in range(n_threads)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    stop.set()
    batcher.join(timeout=10.0)
    assert not errs, errs[0]
    assert eng.stats.submits == n_threads * per_thread
    assert 0 < eng.stats.drains <= eng.stats.submits
    assert eng.stats.batch_occupancy >= 1.0
    assert eng.pending() == 0
