"""`BENCHMARK.json` against the benchmark's contract, every name in it
resolving to its file, and the entry point refusing to run without a TPU."""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import cells  # noqa: F401  (puts the harness on the path)

BENCH = Path(__file__).resolve().parent.parent
ROOT = BENCH.parent
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(SPEC["paths"]) <= 16
    assert all(PATH.match(p) and ".." not in p for p in SPEC["paths"])
    assert 1 <= len(SPEC["command"]) <= 32 and all(map(_text, SPEC["command"]))
    assert isinstance(SPEC["run_seconds"], int) and 1 <= SPEC["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


def test_every_name_and_unit_uses_the_allowed_characters():
    for c in SPEC["configs"]:
        assert NAME.match(c["name"]) and all(NAME.match(k) for k in c["reduced"])
        assert _text(c["source"]) and _text(c["why"])
    for w in SPEC["workloads"]:
        assert NAME.match(w["name"]) and NAME.match(w["config"])
        assert NAME.match(w["traffic"]) and _text(w["why"])
        assert w["chips"] in (1, 4)
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    names = [m["name"] for m in metrics]
    assert len(names) == len(set(names))
    assert len({w["name"] for w in SPEC["workloads"]}) == len(SPEC["workloads"])


def test_entries_have_only_the_contracts_keys():
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _text(m["layer"])


def test_every_cell_resolves_by_name_and_reports_what_it_must():
    import run
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for w in SPEC["workloads"]:
        r = run.resolve(SPEC, w["name"])
        assert callable(getattr(run.loop(r["traffic"]["kind"]), "check"))
        assert all("limit" in v for v in r["limits"].values())
        own = [m for m in SPEC["end_to_end"]
               if w["name"] in m.get("workloads", [w["name"]])]
        assert len(own) >= 2
        layer = [m for m in SPEC["per_layer"] if w["name"] in m["workloads"]]
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in {x["name"] for x in own}
            assert hasattr(run.reader(m["name"]), "read")


def test_configs_are_files_of_their_own_under_paths():
    files = [c["file"] for c in SPEC["configs"]]
    assert len(files) == len(set(files))
    used = {w["config"] for w in SPEC["workloads"]}
    for c in SPEC["configs"]:
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in SPEC["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        for key in c["reduced"]:
            assert key in cfg["published"] and cfg[key] != cfg["published"][key]


def test_a_full_check_fits_its_time_even_with_24_cells():
    rs = SPEC["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_run_without_a_tpu_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(BENCH / "run.py"), "--workload",
                        SPEC["workloads"][0]["name"], "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, cwd=str(ROOT), timeout=120)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())
    assert "no TPU" in p.stderr
