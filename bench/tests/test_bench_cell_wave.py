"""Each wave cell driven end to end on the CPU at a small size: a sound
run is correct and a run whose forward alters its answers is not; at the
configuration's own widths the program reads under the cell's limit and
the three-pass bfloat16 product, written out, reads far above the
program. (The control that sets the limit's upper reading is the chip's
own three-pass `Precision.HIGH`, which only the chip can run:
`bench/calibrate.py`.)"""
import json

import numpy as np
import pytest
from cells import run_small

import run

SPEC = run.load_json(run.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]
         if run.resolve(SPEC, w["name"])["traffic"]["kind"] == "wave"]


def alter_answers(monkeypatch):
    """Every surrogate row altered by 1e-3 where the forward produces it."""
    from repro.core import engine as E
    for name in ("_make_jax_predict", "_make_kernel_predict"):
        make = getattr(E, name)

        def altered(*a, _make=make, **k):
            f = _make(*a, **k)
            return lambda X: f(X) + 1e-3
        monkeypatch.setattr(E, name, altered)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    res = run_small(cell, 2**33 + 1)
    assert res["correct"], res["checks"]
    assert res["failed"] == 0 and res["attempted"] > 0
    assert list(res["checks"]) == ["row_gap", "space_mismatch"]
    json.dumps(res)


@pytest.mark.parametrize("cell", CELLS)
def test_altered_answers_are_not_correct(cell, monkeypatch):
    alter_answers(monkeypatch)
    res = run_small(cell, 2**33 + 2)
    assert not res["correct"]
    assert res["checks"]["row_gap"]["value"] > res["checks"]["row_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_program_passes_and_three_passes_stand_apart_at_full_width(cell):
    r = run.resolve(SPEC, cell)
    from surrogate import Surrogate, draw_configs
    import reference
    s = Surrogate(r["config"], run.import_program())
    cfgs = draw_configs(np.random.default_rng(3), s.sizes, 48, set())
    with reference.host():
        program = s.gaps(cfgs, s.engine(cfgs)).max()
        three_pass = s.control_gaps(cfgs, "high").max()
    assert program < r["limits"]["row_gap"]["limit"]
    assert three_pass > 5 * program
