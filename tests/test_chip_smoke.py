"""chip_smoke.py's phases at tiny sizes on the CPU, and its refusals.

The script itself only runs on a TPU. Here every phase function runs at
a tiny width through the same stages, engines, samplers and service (the
Pallas kernel in interpret mode), the four-chip phases run on four forced
host devices in a subprocess, and `main` is shown to refuse a host with
no TPU without printing its result line.
"""
import json
import os
import shutil
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(REPO))

import chip_smoke  # noqa: E402


def _tiny_cfg(root, **over):
    kw = dict(hidden=16, n_layers=2, dse_budget=128, dse_pop=16,
              use_kernel="on", eval_chunk=64)
    kw.update(over)
    return chip_smoke.smoke_config(48, 1, str(root), **kw)


def test_main_refuses_a_host_without_tpu(capsys):
    rc = chip_smoke.main([])
    out = capsys.readouterr()
    assert rc != 0
    assert '"ok"' not in out.out
    assert "no TPU" in out.err
    assert out.out.splitlines()[-1].startswith("device,platform=cpu")


def test_lone_script_refuses_to_import(tmp_path):
    lone = tmp_path / "chip_smoke.py"
    shutil.copy(REPO / "chip_smoke.py", lone)
    code = ("import sys; sys.path.insert(0, sys.argv[1]); "
            "import chip_smoke; chip_smoke._import_repro()")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    r = subprocess.run([sys.executable, "-c", code, str(tmp_path)],
                       capture_output=True, text=True, env=env, timeout=120)
    assert r.returncode != 0
    assert "no package" in r.stderr
    assert '"ok"' not in r.stdout


def test_smoke_config_keeps_paper_widths(tmp_path):
    paper, cfg = chip_smoke.smoke_config(chip_smoke.LABELS,
                                         chip_smoke.EPOCHS, str(tmp_path))
    assert (cfg.hidden, cfg.n_layers, cfg.dse_budget, cfg.gnn_arch) == (
        300, 5, 20_000, "gsae")
    assert (paper.n_samples, paper.epochs) == (105_000, 100)
    assert (cfg.n_samples, cfg.epochs) == (chip_smoke.LABELS,
                                           chip_smoke.EPOCHS)
    assert cfg.app == "kmeans" and cfg.use_kernel == "auto"


def test_one_chip_phases_tiny(tmp_path):
    """label -> train -> engine -> search -> validate -> serve, each with
    its own check, through one fresh store."""
    from repro.core.artifacts import ArtifactStore

    _, cfg = _tiny_cfg(tmp_path)
    store = ArtifactStore(cfg.artifact_dir)
    ctx, ds = chip_smoke.phase_label(cfg, store)
    assert len(ds.y_raw) == cfg.n_samples
    art = chip_smoke.phase_train(cfg, store, ds, min_r2=None)
    eng = chip_smoke.phase_engine(cfg, store, ctx, ds, art, 128)
    assert eng.backend == "pallas"
    res = chip_smoke.phase_search(cfg, store, ctx, eng)
    assert len(res.pareto_configs) > 0
    result = chip_smoke.phase_validate(cfg, store, 4)
    assert result.engine is eng
    chip_smoke.phase_serve(cfg, store, ctx, eng, 3, 8, 64, 16)
    assert store.stats.as_dict()["misses"]["dataset"] == 1


def test_engine_phase_rejects_the_wrong_backend(tmp_path):
    from repro.core.artifacts import ArtifactStore

    _, cfg = _tiny_cfg(tmp_path, use_kernel="off")
    store = ArtifactStore(cfg.artifact_dir)
    ctx, ds = chip_smoke.phase_label(cfg, store)
    art = chip_smoke.phase_train(cfg, store, ds, min_r2=None)
    with pytest.raises(AssertionError, match="backend 'jax'"):
        chip_smoke.phase_engine(cfg, store, ctx, ds, art, 64)


_FOUR = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=4")
    sys.path.insert(0, sys.argv[1])
    import chip_smoke as S
    from repro.core import pipeline as P
    from repro.core.artifacts import ArtifactStore
    _, cfg = S.smoke_config(48, 0, sys.argv[2], hidden=16, n_layers=2,
                            use_kernel="on", eval_chunk=64)
    store = ArtifactStore(cfg.artifact_dir)
    ctx = P.stage_prune(cfg, store)
    ds = P.stage_dataset(cfg, store, ctx)
    S.phase_sharded_engine(cfg, ctx, ds, 256, 4)
    S.phase_sharded_ranks(4)
    print("FOUR_OK")
""")


def test_four_chip_phases_on_forced_host_devices(tmp_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO / "src")
    r = subprocess.run([sys.executable, "-c", _FOUR, str(REPO),
                        str(tmp_path)], capture_output=True, text=True,
                       env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.splitlines()
    assert lines[-1] == "FOUR_OK"
    chunks = [ln for ln in lines if ln.startswith("sharded_engine,chunk=")]
    assert chunks and chunks[0].endswith(
        "devices_of_successive_chunks=[[0], [1], [2], [3]]")
    assert any("bit_identical=true" in ln for ln in lines
               if ln.startswith("sharded_ranks,fleet"))


def test_result_line_is_the_contract(capsys, monkeypatch, tmp_path):
    """With the device check steered to a TPU and the phases stubbed, the
    last line is exactly the JSON object the contract names."""
    dev = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "_device", lambda: dev)
    monkeypatch.setattr(chip_smoke, "run_one_chip",
                        lambda root, times: times.update(label=0.0))
    from repro.core import artifacts
    monkeypatch.setattr(artifacts, "enable_compilation_cache",
                        lambda: str(tmp_path))
    assert chip_smoke.main([]) == 0
    last = capsys.readouterr().out.splitlines()[-1]
    assert json.loads(last) == {"ok": True, "device": dev}
