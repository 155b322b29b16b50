"""The functional models behind the truth tables and the pruning are
computed on the host's CPU device, whatever the default device is.

The float unit families (mitchell, drum, pwl, newton) truncate float32
``log2``/``exp2``/division results to integers, so a device that rounds one
ulp elsewhere moves a table entry by one. `units.host` pins
`UnitInstance.lut` (hence `library.stacked_lut`, labeling and the probe)
and `library.error_metrics` (hence pruning) to ``jax.devices("cpu")[0]``.
Shown here in a subprocess with eight forced host devices (the idiom of
tests/test_engine_sharded.py) and the default device set to the fourth:
every unit evaluation lands on the first CPU device, and the tables of
`kmeans-paper`'s `mul8` and `sqrt18` entries still equal the benchmark's
plain reference (`bench/reference.py`) entry for entry.
"""
import json
import os
import subprocess
import sys
import textwrap

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
        " --xla_force_host_platform_device_count=8")
    import json
    import sys
    import jax
    import jax.numpy as jnp
    import numpy as np

    jax.config.update("jax_default_device", jax.devices()[3])
    sys.path.insert(0, os.path.join(%(root)r, "bench"))
    import reference as ref
    from repro.accel import library as lib
    from repro.accel import units
    from repro.core import pruning

    # every unit function's output, by the devices it was computed on
    seen = []
    fn = units.UnitInstance.fn

    def spied(self):
        f = fn(self)

        def call(*args):
            out = f(*args)
            seen.append(sorted(str(d) for d in out.devices()))
            return out
        return call
    units.UnitInstance.fn = spied

    out = {"devices": jax.device_count(),
           "default": str(jnp.zeros(1).devices().pop())}
    pruned, _ = pruning.prune_library(theta=0.15)
    out["characterized_on"] = sorted({d for s in seen for d in s})
    seen.clear()
    for kind in ("mul8", "sqrt18"):
        ea, eb = lib.lut_domain("kmeans", kind)
        entries = tuple(pruned[kind])
        table = lib.stacked_lut(entries, ea, eb)
        space = ref.pruned(kind, 0.15)
        with ref.host():
            a = jnp.repeat(jnp.arange(1 << ea, dtype=jnp.int32), 1 << eb)
            b = jnp.tile(jnp.arange(1 << eb, dtype=jnp.int32), 1 << ea)
            want = np.concatenate([np.asarray(e.unit.fn()(a, b)
                                              ).astype(np.int32)
                                   for e in space])
        out[kind] = {"entries": len(entries),
                     "names_match": [e.inst.name for e in entries]
                     == [e.unit.name for e in space],
                     "shape": list(table.shape),
                     "equal": bool(np.array_equal(table, want))}
    out["tables_on"] = sorted({d for s in seen for d in s})
    out["lut_device"] = sorted(
        str(d) for d in pruned["mul8"][1].inst.lut(9, 9).devices())
    print(json.dumps(out))
""")


def test_tables_and_characterization_run_on_the_host_cpu():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", _SCRIPT % {"root": ROOT}],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["devices"] == 8 and out["default"] == "TFRT_CPU_3"
    assert out["characterized_on"] == ["TFRT_CPU_0"]
    assert out["tables_on"] == ["TFRT_CPU_0"]
    assert out["lut_device"] == ["TFRT_CPU_0"]
    for kind, width in (("mul8", 1 << 18), ("sqrt18", 1 << 20)):
        got = out[kind]
        assert got["names_match"] and got["equal"], kind
        assert got["entries"] > 1
        assert got["shape"] == [got["entries"] * width]
