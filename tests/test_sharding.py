"""Sharding-plan tests on a multi-device host mesh (subprocess: jax locks
the device count at first init, so these run with their own XLA_FLAGS)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.configs import REDUCED_ARCHS
    from repro.configs.base import ShapeConfig
    from repro.launch import steps as S
    from repro.data.tokens import TokenPipeline

    arch = sys.argv[1]
    kind = sys.argv[2]
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = REDUCED_ARCHS[arch]
    if kind == "train":
        shape = ShapeConfig("t", 16, 4, "train", grad_accum=2)
    else:
        shape = ShapeConfig("t", 32, 4, "decode")
    step_fn, arg_specs, in_sh, out_sh, donate = S.plan(cfg, shape, mesh)
    with mesh:
        jitted = jax.jit(step_fn, in_shardings=in_sh, out_shardings=out_sh)
        compiled = jitted.lower(*arg_specs).compile()
        # actually run a real step on the 8 host devices
        if kind == "train":
            import numpy as np
            from repro.models import transformer
            from repro.optim import adamw
            table = transformer.build_param_table(cfg)
            psh = in_sh[0]
            params = jax.jit(table.init, out_shardings=psh)(
                jax.random.PRNGKey(0))
            opt = adamw.init(params)
            pipe = TokenPipeline(cfg.vocab_size, 16, 4)
            extras = {k: v for k, v in arg_specs[2].items()
                      if k not in ("tokens", "labels")}
            batch = pipe.batch_at(0, extras)
            p2, o2, m = jitted(params, opt, batch)
            assert bool(jnp.isfinite(m["loss"])), m
            print(json.dumps({"ok": True, "loss": float(m["loss"])}))
        else:
            print(json.dumps({"ok": True}))
""")


@pytest.mark.parametrize("arch,kind", [
    ("granite-3-2b", "train"), ("mixtral-8x7b", "train"),
    ("rwkv6-3b", "train"), ("hymba-1.5b", "decode"),
    ("granite-3-2b", "decode"), ("whisper-large-v3", "train"),
])
def test_sharded_step_on_8_devices(arch, kind, tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _SCRIPT, arch, kind],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["ok"]


_TP_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, sys
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.configs import REDUCED_ARCHS
    from repro.configs.base import ShapeConfig
    from repro.launch import steps as S
    from repro.distributed import meshes as M
    from repro.models import transformer
    from repro.optim import adamw
    from repro.data.tokens import TokenPipeline
    import numpy as np

    preset = sys.argv[1]
    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    cfg = REDUCED_ARCHS["granite-3-2b"]
    shape = ShapeConfig("t", 32, 8, "train", grad_accum=2)
    pipe = TokenPipeline(cfg.vocab_size, 32, 8)
    losses = {}
    for name in ("baseline", preset):
        step_fn, arg_specs, in_sh, out_sh, dn = S.plan(
            cfg, shape, mesh, rules=M.PRESETS[name])
        table = transformer.build_param_table(cfg)
        with mesh:
            params = jax.jit(table.init, out_shardings=in_sh[0])(
                jax.random.PRNGKey(0))
            opt = adamw.init(params)
            jitted = jax.jit(step_fn, in_shardings=in_sh,
                             out_shardings=out_sh)
            _, _, m = jitted(params, opt, pipe.batch_at(0))
            losses[name] = float(m["loss"])
    # Presets are bit-identical on this host since (a) partitionable
    # threefry made param init sharding-invariant (repro/__init__.py) and
    # (b) head-aligned flat sharding avoids the XLA rope miscompile
    # (meshes.spec_for head_dim fallback). The 5e-3 slack is retained only
    # for cross-platform fusion/rounding differences, NOT for layout
    # drift: values well above float noise mean a real regression.
    assert abs(losses["baseline"] - losses[preset]) < 5e-3, losses
    print(json.dumps({"ok": True, **losses}))
""")


@pytest.mark.parametrize("preset", ["tp", "cp"])
def test_perf_presets_match_baseline(preset):
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _TP_SCRIPT, preset],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert json.loads(r.stdout.strip().splitlines()[-1])["ok"]


_ALIGN_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    from jax.sharding import AxisType
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.distributed import meshes as M

    mesh = jax.make_mesh((2, 4), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    # kv_flat=32 over model(4): 8 cols/device splits head_dim=16 -> replicate
    s = M.spec_for(mesh, (64, 32), ("embed", "kv_flat"), M.BASE_RULES,
                   head_dim=16)
    assert s == P("data", None), s
    # heads_flat=64 over model(4): 16 cols/device = whole heads -> shard
    s = M.spec_for(mesh, (64, 64), ("embed", "heads_flat"), M.BASE_RULES,
                   head_dim=16)
    assert s == P("data", "model"), s
    # no head_dim metadata: plain divisibility behavior is unchanged
    s = M.spec_for(mesh, (64, 32), ("embed", "kv_flat"), M.BASE_RULES)
    assert s == P("data", "model"), s

    # Value-level regression for the layout that spec_for now emits: a
    # rope-style half-split on (B,S,H,D) tensors built from flat-sharded
    # projections must match the fully-replicated computation. (With the
    # shard boundary INSIDE a head, jax 0.4.37's CPU partitioner
    # miscompiled this: k off by O(1), reductions inflated by the
    # model-axis size — which is why spec_for falls back to replication.)
    B, S, D = 4, 8, 16
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((B, S, 64)), jnp.bfloat16)
    W = jnp.asarray(rng.standard_normal((64, 64)) * 0.1, jnp.bfloat16)
    ang = jnp.asarray(rng.standard_normal((B, S, D // 2)), jnp.float32)

    def rope(x):
        dt = x.dtype
        x = x.astype(jnp.float32)
        x1, x2 = x[..., :D // 2], x[..., D // 2:]
        cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
        return jnp.concatenate([x1 * cos - x2 * sin,
                                x2 * cos + x1 * sin], -1).astype(dt)

    def f(x, W, spec):
        Wc = jax.lax.with_sharding_constraint(W, NamedSharding(mesh, spec))
        q = jnp.matmul(x, Wc,
                       preferred_element_type=jnp.float32).astype(x.dtype)
        q = rope(q.reshape(B, S, 4, D))
        s = jnp.einsum("bqhd,bshd->bhqs", q, q,
                       preferred_element_type=jnp.float32)
        return s.sum(), q

    with mesh:
        spec = M.spec_for(mesh, W.shape, ("embed", "heads_flat"),
                          M.BASE_RULES, head_dim=D)
        t_ref, q_ref = jax.jit(lambda x, W: f(x, W, P(None, None)))(x, W)
        t_sh, q_sh = jax.jit(lambda x, W: f(x, W, spec))(x, W)
        np.testing.assert_allclose(np.asarray(q_sh, np.float32),
                                   np.asarray(q_ref, np.float32),
                                   atol=1e-2)
        assert abs(float(t_sh) - float(t_ref)) < 1.0, (t_sh, t_ref)
    print(json.dumps({"ok": True}))
""")


def test_flat_head_sharding_alignment():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    r = subprocess.run([sys.executable, "-c", _ALIGN_SCRIPT],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    assert json.loads(r.stdout.strip().splitlines()[-1])["ok"]
