"""The CPU side of the `kmeans-paper` finding: on the CPU the program's
truth tables of the LUT units (`mul8`, `sqrt18`) equal the reference's unit
functions over the same operand domain, entry by entry; on the chip they
do not (PERF.md, Open questions)."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

import cells  # noqa: F401  (puts the harness on the path)
import reference as ref
import run

CFG = json.loads((run.BENCH / "configs" / "kmeans-paper.json").read_text())


@pytest.mark.parametrize("kind", ["mul8", "sqrt18"])
def test_program_tables_equal_the_reference_on_the_cpu(kind):
    run.import_program()
    from repro.accel import library as lib
    from repro.core import pipeline
    entries = pipeline.app_context(CFG["app"], float(CFG["theta"])).entries
    ea, eb = lib.lut_domain(CFG["app"], kind)
    table = np.asarray(lib.stacked_lut(tuple(entries[kind]), ea, eb))
    space = ref.pruned(kind, float(CFG["theta"]))
    assert [e.inst.name for e in entries[kind]] == [e.unit.name for e in space]
    a = jnp.repeat(jnp.arange(1 << ea, dtype=jnp.int32), 1 << eb)
    b = jnp.tile(jnp.arange(1 << eb, dtype=jnp.int32), 1 << ea)
    want = np.concatenate([np.asarray(e.unit.fn()(a, b)).astype(np.int32)
                           for e in space])
    assert np.array_equal(table, want)
