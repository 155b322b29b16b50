"""LM-framework benchmarks: kernels, train/decode step timing (reduced
configs on CPU), roofline summary from the dry-run, ApproxPilot-LM DSE."""
from __future__ import annotations

import json
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np


def _time(fn, *args, iters=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.time()
    for _ in range(iters):
        out = jax.block_until_ready(fn(*args))
    return (time.time() - t0) / iters * 1e6   # us


def bench_kernels():
    print("# kernels: pure-jnp oracle timing (pallas runs interpret on CPU;"
          " native path is TPU)")
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    B, N, F, Fo = 64, 32, 21, 64
    adj = jnp.asarray(rng.random((B, N, N)), jnp.float32)
    h = jnp.asarray(rng.standard_normal((B, N, F)), jnp.float32)
    ws = jnp.asarray(rng.standard_normal((F, Fo)) * .1, jnp.float32)
    wn = jnp.asarray(rng.standard_normal((F, Fo)) * .1, jnp.float32)
    b = jnp.zeros(Fo, jnp.float32)
    us = _time(jax.jit(lambda *a: ops.gnn_mp(*a, backend="ref")),
               adj, h, ws, wn, b)
    flops = B * N * (N + 2 * F) * Fo * 2
    print(f"kernel,gnn_mp_ref,{us:.0f}us_per_call,"
          f"gflops={flops / us / 1e3:.1f}")

    q = jnp.asarray(rng.standard_normal((1, 4, 256, 32)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 2, 256, 32)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 2, 256, 32)), jnp.float32)
    us = _time(jax.jit(lambda *a: ops.flash_attention(*a, backend="ref")),
               q, k, v)
    print(f"kernel,flash_attention_ref,{us:.0f}us_per_call,shape=1x4x256x32")

    aa = jnp.asarray(rng.random((512, 64)) * .9, jnp.float32)
    bbb = jnp.asarray(rng.standard_normal((512, 64)), jnp.float32)
    y0 = jnp.zeros(64, jnp.float32)
    us = _time(jax.jit(lambda *x: ops.ssm_scan(*x, backend="ref")),
               aa, bbb, y0)
    print(f"kernel,ssm_scan_ref,{us:.0f}us_per_call,T=512,D=64")


def bench_train_decode_steps():
    print("# reduced-config step timing on CPU (structural, not TPU perf)")
    from repro.configs import REDUCED_ARCHS
    from repro.configs.base import ShapeConfig
    from repro.launch import steps as steps_lib
    from repro.models import transformer, decoding
    from repro.optim import adamw
    for arch in ("granite-3-2b", "mixtral-8x7b", "rwkv6-3b"):
        cfg = REDUCED_ARCHS[arch]
        params = transformer.build_param_table(cfg).init(
            jax.random.PRNGKey(0))
        opt = adamw.init(params)
        shape = ShapeConfig("b", 32, 4, "train")
        step = jax.jit(steps_lib.make_train_step(cfg, shape))
        batch = {"tokens": jnp.zeros((4, 32), jnp.int32),
                 "labels": jnp.zeros((4, 32), jnp.int32)}
        if cfg.n_vision_tokens:
            batch["vision_embeds"] = jnp.zeros(
                (4, cfg.n_vision_tokens, cfg.d_model), jnp.bfloat16)
        us = _time(lambda p, o, b: step(p, o, b)[2]["loss"], params, opt,
                   batch, iters=3, warmup=1)
        toks = 4 * 32
        print(f"lm,{arch}/train_step,{us:.0f}us_per_call,"
              f"tok_s={toks / us * 1e6:.0f}")
        dshape = ShapeConfig("d", 64, 4, "decode")
        cache = decoding.init_cache(cfg, dshape)
        dstep = jax.jit(lambda p, c, t, s: decoding.decode_step(
            cfg, p, c, t, s))
        tk = jnp.zeros((4, 1), jnp.int32)
        us = _time(lambda p, c: dstep(p, c, tk, jnp.int32(3))[0], params,
                   cache, iters=3, warmup=1)
        print(f"lm,{arch}/decode_step,{us:.0f}us_per_call,"
              f"tok_s={4 / us * 1e6:.0f}")


def bench_roofline_summary():
    print("# roofline summary (single-pod baseline, from dry-run artifacts)")
    from repro.launch import roofline
    try:
        rows = roofline.table("16x16", "baseline")
    except FileNotFoundError:
        print("roofline,missing,run `python -m repro.launch.dryrun` first")
        return
    for r in rows:
        print(f"roofline,{r['arch']}/{r['shape']},"
              f"dominant={r['dominant']},"
              f"frac={r['roofline_fraction'] * 100:.1f}%,"
              f"ratio6nd={r['flops_ratio']:.2f}")


def bench_lm_bridge():
    print("# ApproxPilot-LM: per-op precision DSE (beyond-paper)")
    from repro.configs import get_arch, get_shape
    from repro.core import lm_bridge
    # two-stage GNN surrogate on the LM op graph (stage-1 = critical op)
    m, _ = lm_bridge.train_surrogate(get_arch("qwen2.5-32b"),
                                     get_shape("train_4k"),
                                     n_samples=400, epochs=40)
    relabel = {"area": "log_time", "power": "log_hbm", "latency": "penalty"}
    row = ",".join(f"{relabel.get(k, k)}_r2={v['r2']:.3f}"
                   for k, v in m.items() if k in relabel)
    print(f"lm_bridge,gnn_surrogate,{row},"
          f"critical_op_acc={m['critical_path']['accuracy']:.3f}")
    for arch, shape in (("granite-3-2b", "decode_32k"),
                        ("qwen1.5-110b", "train_4k")):
        t0 = time.time()
        out = lm_bridge.run_dse(get_arch(arch), get_shape(shape),
                                budget=800)
        dt = time.time() - t0
        base = out["baseline"]
        if out["best"]:
            _, obj = out["best"]
            speedup = base["time"] / max(obj[0], 1e-12)
            print(f"lm_bridge,{arch}/{shape},crit_op={base['critical_op']},"
                  f"speedup={speedup:.2f}x,hbm={base['hbm_gb']:.2f}->"
                  f"{obj[1]:.2f}GB,penalty={obj[2]:.1f},time_s={dt:.1f}")
