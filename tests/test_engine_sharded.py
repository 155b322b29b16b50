"""Multi-device + pipelined `SurrogateEngine` execution.

Two properties are proven here:

* **Spreading chunks over devices is invisible in values** — an engine
  built with ``devices=0`` (all local devices) on a forced-8-device host
  (`XLA_FLAGS=--xla_force_host_platform_device_count=8`, the same
  subprocess idiom as tests/test_islands_batched.py) puts successive
  chunks on every device and produces rows bit-identical to a 1-device
  host, for both the direct ``__call__`` path and the cross-request
  ``submit``/``drain`` path, with the memo cache on and off. Each device
  runs the single-device program on whole chunks.
* **Overlap is invisible in values and visible in timings** — the
  pipelined chunk executor (featurize worker + async dispatch + deferred
  collect) returns exactly the serial path's rows while
  ``stats.overlap_fraction``/``featurize_s``/``dispatch_s``/``collect_s``
  record the interleaving; phase failures heal through the composed
  backend call.

Satellites of the same PR ride along: the explicit ``chunk_size=None``
no-chunking mode (`queued_view`'s former ``1 << 30`` sentinel) and the
``padded_fraction`` stat + ragged-padding warning.
"""
import json
import os
import subprocess
import sys
import textwrap
import time
import warnings

import numpy as np
import pytest

from repro.core.engine import (PADDING_WARN_FRACTION, PipelinedBackend,
                               SurrogateEngine)


# --------------------------------------------------------------------------
# a host-only pipelined backend (no jax): objectives are exact functions of
# the config, so every path must agree bit-for-bit
# --------------------------------------------------------------------------

def _rows_for(configs):
    a = np.asarray(configs, np.float64)
    return np.stack([a.sum(1), a.max(1), a.min(1) - 1.0, a.mean(1)], 1)


def _fake_pipeline(prepare_sleep=0.0, collect_sleep=0.0, log=None):
    def prepare(configs):
        if prepare_sleep:
            time.sleep(prepare_sleep)
        if log is not None:
            log.append(("prepare", len(configs)))
        return np.asarray(configs, np.float64)

    def dispatch(X):
        if log is not None:
            log.append(("dispatch", len(X)))
        return X

    def collect(handle):
        if collect_sleep:
            time.sleep(collect_sleep)
        if log is not None:
            log.append(("collect", len(handle)))
        return _rows_for(handle)

    return PipelinedBackend(prepare, dispatch, collect)


def _configs(n, width=4, hi=9, seed=0):
    rng = np.random.default_rng(seed)
    return [tuple(int(v) for v in rng.integers(0, hi, width))
            for _ in range(n)]


# --------------------------------------------------------------------------
# overlap: bit-identity + per-wave timings
# --------------------------------------------------------------------------

def test_overlap_rows_bit_identical_to_serial():
    cfgs = _configs(40)
    on = SurrogateEngine(_fake_pipeline(), chunk_size=8)
    off = SurrogateEngine(_fake_pipeline(), chunk_size=8, overlap=False)
    assert on.overlap and not off.overlap
    r_on, r_off = on(cfgs), off(cfgs)
    np.testing.assert_array_equal(r_on, r_off)
    np.testing.assert_array_equal(r_on, _rows_for(cfgs))


def test_overlap_fraction_shows_featurize_compute_interleaving():
    """With K chunks, every chunk after the first featurizes while prior
    chunks are in flight: overlapped_s must cover ~ (K-1)/K of the
    featurize time, and all three phase timers must be populated."""
    cfgs = _configs(64)
    eng = SurrogateEngine(_fake_pipeline(prepare_sleep=0.02,
                                         collect_sleep=0.005),
                          chunk_size=16)
    eng(cfgs)
    d = eng.stats.as_dict()
    assert d["chunks"] == 4
    assert d["featurize_s"] >= 4 * 0.02
    assert d["collect_s"] >= 4 * 0.005
    assert d["dispatch_s"] >= 0.0
    # 3 of 4 chunk preparations ran while earlier chunks were in flight
    assert d["overlapped_s"] > 0
    assert 0.3 < d["overlap_fraction"] <= 1.0
    assert eng.stats.overlap_fraction == pytest.approx(
        d["overlap_fraction"], abs=1e-3)


def test_single_chunk_call_never_overlaps():
    """One chunk = nothing to hide behind: the serial path runs and the
    overlap timers stay zero."""
    eng = SurrogateEngine(_fake_pipeline(), chunk_size=64)
    eng(_configs(10))
    d = eng.stats.as_dict()
    assert d["chunks"] == 1
    assert d["overlapped_s"] == 0.0
    assert d["overlap_fraction"] == 0.0


def test_overlap_collect_failure_heals_through_composed_backend():
    """A transient collect fault on one chunk re-evaluates that chunk
    through the composed backend (retry semantics of the serial path);
    rows stay exact."""
    state = {"failed": False}
    pb = _fake_pipeline()
    real_collect = pb.collect

    def flaky_collect(handle):
        if not state["failed"]:
            state["failed"] = True
            raise RuntimeError("transient transfer fault")
        return real_collect(handle)

    pb.collect = flaky_collect
    cfgs = _configs(32)
    eng = SurrogateEngine(pb, chunk_size=8)
    np.testing.assert_array_equal(eng(cfgs), _rows_for(cfgs))
    assert state["failed"]


def test_overlap_prepare_failure_propagates_like_serial():
    """A deterministic featurization error must raise identically with
    and without the pipeline (the worker forwards it, the fallback hits
    it again)."""
    def bad_prepare(configs):
        raise ValueError("bad feature table")

    pb = PipelinedBackend(bad_prepare, lambda x: x, _rows_for)
    cfgs = _configs(32)
    for overlap in (True, False):
        eng = SurrogateEngine(pb, chunk_size=8, overlap=overlap,
                              nan_guard=False)
        with pytest.raises(ValueError, match="bad feature table"):
            eng(cfgs)


def test_pipelined_backend_composes_to_plain_batch_fn():
    pb = _fake_pipeline()
    cfgs = _configs(6)
    np.testing.assert_array_equal(pb(cfgs), _rows_for(cfgs))


def test_reset_stats_preserves_device_width():
    pb = _fake_pipeline()
    pb.devices = 4
    eng = SurrogateEngine(pb, chunk_size=8)
    assert eng.stats.devices == 4
    eng(_configs(4))
    eng.reset_stats()
    assert eng.stats.devices == 4
    assert eng.stats.as_dict()["devices"] == 4


# --------------------------------------------------------------------------
# explicit no-chunking mode (queued_view's former 1<<30 sentinel)
# --------------------------------------------------------------------------

def test_chunk_size_none_is_one_backend_call():
    calls = []

    def backend(cfgs):
        calls.append(len(cfgs))
        return _rows_for(cfgs)

    eng = SurrogateEngine(backend, chunk_size=None)
    eng([(i, i % 7, i % 5, 1) for i in range(1000)])  # all distinct
    assert calls == [1000]
    assert eng.stats.chunks == 1


def test_chunk_size_none_rejects_fixed_shape():
    with pytest.raises(ValueError, match="fixed_shape needs chunking"):
        SurrogateEngine(_rows_for, chunk_size=None, fixed_shape=True)
    with pytest.raises(ValueError, match="chunk_size must be >= 1"):
        SurrogateEngine(_rows_for, chunk_size=0)


def test_queued_view_uses_no_chunking_mode():
    eng = SurrogateEngine(_rows_for, chunk_size=8)
    view = eng.queued_view()
    assert view.chunk_size is None
    assert not view.fixed_shape


# --------------------------------------------------------------------------
# padded_fraction + ragged-padding warning
# --------------------------------------------------------------------------

def test_padded_fraction_reported():
    eng = SurrogateEngine(_rows_for, chunk_size=8, fixed_shape=True)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng(_configs(9))                      # 8 + pad(1 -> bucket 1)
    d = eng.stats.as_dict()
    assert d["padded"] == 0                   # 9 = 8 + bucket(1): no waste
    eng.reset_stats()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        eng(_configs(13, seed=1))             # 8 + pad(5 -> bucket 8)
    d = eng.stats.as_dict()
    assert d["padded"] == 3
    assert d["padded_fraction"] == pytest.approx(3 / 16)
    assert eng.stats.padded_fraction == pytest.approx(3 / 16)


def test_ragged_padding_warns_once_above_threshold():
    eng = SurrogateEngine(_rows_for, chunk_size=512, fixed_shape=True)
    with pytest.warns(RuntimeWarning, match="ragged-chunk padding"):
        eng(_configs(5))                      # bucket 8: 3/8 > 25%
    with warnings.catch_warnings():
        warnings.simplefilter("error")        # second wave: no re-warn
        eng(_configs(5, seed=2))


def test_no_warning_below_threshold():
    eng = SurrogateEngine(_rows_for, chunk_size=512, fixed_shape=True)
    assert PADDING_WARN_FRACTION == 0.25
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng(_configs(7))                      # bucket 8: 1/8 < 25%


# --------------------------------------------------------------------------
# sharded GNN engine: device-count invariance (subprocess, forced devices)
# --------------------------------------------------------------------------

_DEVICE_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=%d")
    import json
    import numpy as np
    import jax
    from repro.accel import apps as apps_lib
    from repro.core import dataset as ds_lib, gnn, models, pruning
    from repro.core.engine import SurrogateEngine

    pruned, _ = pruning.prune_library()
    app = apps_lib.APPS["sobel"]
    entries = {k: pruned[k] for k in {n.kind for n in app.unit_nodes}}
    ds = ds_lib.build("sobel", n_samples=24, seed=0, lib_entries=entries)
    two_cfg = models.TwoStageConfig(gnn=gnn.GNNConfig(
        arch="gsae", n_layers=2, hidden=16, feature_dim=ds.x.shape[-1]))
    # deterministic untrained params: identical across subprocesses by
    # construction, so any row divergence is the sharded engine's fault
    params = models.init(jax.random.PRNGKey(0), two_cfg)
    rng = np.random.default_rng(1)
    sizes = [len(entries[n.kind]) for n in app.unit_nodes]
    cfg_a = [tuple(int(rng.integers(0, s)) for s in sizes)
             for _ in range(48)]
    cfg_b = [tuple(int(rng.integers(0, s)) for s in sizes)
             for _ in range(48)]

    def rows(arr):
        return [[repr(float(v)) for v in r] for r in np.asarray(arr)]

    out = {"devices": jax.device_count()}
    for label, cache in (("memo", True), ("nomemo", False)):
        eng = SurrogateEngine.from_gnn(two_cfg, params, ds, app, entries,
                                       chunk_size=16, devices=0,
                                       cache=cache)
        out["shard_width_" + label] = eng.devices
        pb = eng._pipeline
        X = pb.prepare(cfg_a[:16])
        out["chunk_devices_" + label] = len(
            {d.id for _ in range(jax.device_count())
             for d in pb.dispatch(X).devices()})
        out["call_" + label] = rows(eng(cfg_a))
        # cross-request drain path: queued submissions coalesce into one
        # fused wave
        futs = [eng.submit(cfg_b[i:i + 12]) for i in range(0, 48, 12)]
        assert eng.drain() == 4
        out["drain_" + label] = rows(np.concatenate(
            [f.result(timeout=60) for f in futs], 0))
    print(json.dumps(out))
""")


def _run_with_devices(n):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _DEVICE_SCRIPT % n],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_sharded_drain_bit_identical_across_1_and_8_devices():
    """Acceptance: an engine spreading its chunks over 8 forced host
    devices serves the exact float rows of the single-device engine —
    for __call__ and submit/drain, memo cache on and off."""
    one = _run_with_devices(1)
    eight = _run_with_devices(8)
    assert one["devices"] == 1 and eight["devices"] == 8
    assert one["shard_width_memo"] == 1
    assert eight["shard_width_memo"] == 8
    assert one["chunk_devices_memo"] == 1
    assert eight["chunk_devices_memo"] == 8    # every device gets chunks
    for key in ("call_memo", "call_nomemo", "drain_memo", "drain_nomemo"):
        assert one[key] == eight[key], f"{key} diverged across devices"
    # the two paths agree with each other as well (same memoized rows)
    assert one["call_memo"] == one["call_nomemo"]
