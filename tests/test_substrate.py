"""Checkpointing, fault tolerance, compression, token pipeline, HLO profile."""
import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro import checkpointing as ck
from repro.data.tokens import TokenPipeline
from repro.distributed import compression as comp
from repro.distributed.fault import (FaultInjector, HealthMonitor,
                                     HostFailure, elastic_plan)


def _tree():
    return {"a": jnp.arange(12.0).reshape(3, 4),
            "b": {"c": jnp.ones((5,), jnp.int32),
                  "d": jnp.full((2, 2), 0.5, jnp.bfloat16)}}


def test_checkpoint_roundtrip(tmp_path):
    t = _tree()
    ck.save(tmp_path, 3, t)
    restored, step = ck.restore(tmp_path, t)
    assert step == 3
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(restored)):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))


def test_checkpoint_retention_and_latest(tmp_path):
    t = _tree()
    for s in (1, 2, 3, 4, 5):
        ck.save(tmp_path, s, t, keep_last=2)
    assert ck.all_steps(tmp_path) == [4, 5]
    assert ck.latest_step(tmp_path) == 5


def test_checkpoint_incomplete_ignored(tmp_path):
    t = _tree()
    ck.save(tmp_path, 1, t)
    # simulate a crash mid-write: directory without .complete marker
    bad = tmp_path / "step_9"
    bad.mkdir()
    (bad / "manifest.json").write_text("{}")
    assert ck.latest_step(tmp_path) == 1


def test_async_checkpointer(tmp_path):
    c = ck.AsyncCheckpointer(tmp_path)
    t = _tree()
    c.save(1, t)
    c.save(2, t)
    c.close()
    assert ck.latest_step(tmp_path) == 2


def test_fault_injector_and_monitor():
    inj = FaultInjector(crash_at=[3])
    inj.check(1)
    with pytest.raises(HostFailure):
        inj.check(3)
    inj.check(3)   # fires once
    mon = HealthMonitor(straggler_factor=3.0)
    for s in range(6):
        mon.record(s, 0.01)
    assert mon.record(6, 0.2) is True
    assert 6 in mon.stragglers


@settings(max_examples=40, deadline=None)
@given(st.integers(1, 512), st.sampled_from([32, 64, 128, 256]))
def test_elastic_plan_properties(n_devices, global_batch):
    plan = elastic_plan(n_devices, global_batch)
    assert plan["data"] * plan["model"] == n_devices
    assert global_batch % plan["data"] == 0 or plan["grad_accum"] >= 1


def test_train_restart_recovers(tmp_path):
    """End-to-end: crash at step 6, restart resumes from checkpoint."""
    from repro.configs import REDUCED_ARCHS
    from repro.configs.base import ShapeConfig
    from repro.launch.train import train
    cfg = REDUCED_ARCHS["granite-3-2b"]
    shape = ShapeConfig("t", 16, 2, "train")
    inj = FaultInjector(crash_at=[6])
    out = train(cfg, shape, 10, str(tmp_path), injector=inj,
                ckpt_every=2, log_every=0)
    assert out["final_step"] == 10
    assert ck.latest_step(tmp_path) is not None


# --------------------------------------------------------------------------
# gradient compression
# --------------------------------------------------------------------------

def test_quantize_error_bound():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal(1000) * 3, jnp.float32)
    q, s = comp.quantize(g)
    err = np.abs(np.asarray(comp.dequantize(q, s) - g))
    assert err.max() <= float(s) * 0.5 + 1e-6


def test_error_feedback_convergence():
    """EF-int8 SGD must reach (near) the same loss as fp32 SGD."""
    rng = np.random.default_rng(0)
    X = rng.standard_normal((128, 8)).astype(np.float32)
    w_true = rng.standard_normal(8).astype(np.float32)
    y = X @ w_true

    def run(compressed: bool):
        w = jnp.zeros(8, jnp.float32)
        res = None
        for _ in range(300):
            g = 2 * X.T @ (np.asarray(X @ w) - y) / len(X)
            g = jnp.asarray(g)
            if compressed:
                (cg,), res_ = comp.compress_tree((g,), res)
                res = res_
                g = comp.decompress_tree((cg,))[0]
            w = w - 0.05 * g
        return float(jnp.mean((jnp.asarray(X) @ w - jnp.asarray(y)) ** 2))

    assert run(True) < run(False) * 2 + 1e-4


# --------------------------------------------------------------------------
# token pipeline
# --------------------------------------------------------------------------

def test_token_pipeline_deterministic():
    p = TokenPipeline(vocab_size=64, seq_len=16, global_batch=4, seed=1)
    b1 = p.batch_at(7)
    b2 = p.batch_at(7)
    assert np.array_equal(b1["tokens"], b2["tokens"])
    b3 = p.batch_at(8)
    assert not np.array_equal(b1["tokens"], b3["tokens"])
    # labels are next-token shifted
    assert np.array_equal(b1["labels"][:, :-1], b1["tokens"][:, 1:])
    assert (b1["labels"][:, -1] == -1).all()


# --------------------------------------------------------------------------
# hlo profiler
# --------------------------------------------------------------------------

def test_hlo_profile_matches_cost_analysis_loop_free():
    from repro.launch import hlo_profile

    @jax.jit
    def f(a, b):
        return jax.nn.relu(a @ b)

    a = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    b = jax.ShapeDtypeStruct((128, 32), jnp.float32)
    compiled = f.lower(a, b).compile()
    prof = hlo_profile.analyze(compiled.as_text())
    assert prof["dot_flops"] == pytest.approx(2 * 64 * 128 * 32, rel=0.01)


def test_hlo_profile_trip_count_multiplication():
    from repro.launch import hlo_profile

    @jax.jit
    def f(x, w):
        def body(c, _):
            return jax.nn.relu(c @ w), None
        out, _ = jax.lax.scan(body, x, None, length=5)
        return out

    x = jax.ShapeDtypeStruct((32, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    compiled = f.lower(x, w).compile()
    prof = hlo_profile.analyze(compiled.as_text())
    # 5 iterations x 2*32*64*64 flops
    assert prof["dot_flops"] == pytest.approx(5 * 2 * 32 * 64 * 64, rel=0.05)
    # XLA's own analysis counts the body once: we must exceed it
    ca = compiled.cost_analysis()
    assert prof["dot_flops"] > ca["flops"] * 2


def test_int8_kv_cache_decode_parity():
    import jax
    import jax.numpy as jnp
    from repro.configs import REDUCED_ARCHS
    from repro.configs.base import ShapeConfig
    from repro.models import decoding, transformer
    cfg = REDUCED_ARCHS["granite-3-2b"]
    params = transformer.build_param_table(cfg).init(jax.random.PRNGKey(0))
    shape = ShapeConfig("d", 16, 2, "decode")
    rng = np.random.default_rng(0)
    c_bf = decoding.init_cache(cfg, shape)
    c_i8 = decoding.init_cache(cfg, shape, kv_int8=True)
    for pos in range(6):
        tok = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 1)), jnp.int32)
        l1, c_bf = decoding.decode_step(cfg, params, c_bf, tok,
                                        jnp.int32(pos))
        l2, c_i8 = decoding.decode_step(cfg, params, c_i8, tok,
                                        jnp.int32(pos))
        assert float(jnp.abs(l1 - l2).max()) < 0.3
