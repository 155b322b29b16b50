"""What the benchmark's CPU tests share: the harness's modules on the path,
and each cell cut to a size the CPU holds in seconds (the chip runs them at
the sizes in ``BENCHMARK.json``). Imported first by every test file here."""
from __future__ import annotations

import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent.parent
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))

SMALL_CONFIG = {"hidden": 16, "n_layers": 2, "eval_chunk": 32}
SMALL_TRAFFIC = {"wave": {"wave": 64, "max_rate": 640, "check_rows": 16}}


def run_small(workload: str, seed: int, seconds: float = 1.0) -> dict:
    """One run of a cell at CPU-test size, the chip check skipped."""
    import run
    kind = run.resolve(run.load_json(run.ROOT / "BENCHMARK.json"),
                       workload)["traffic"]["kind"]
    return run.run_cell(workload, seed, seconds, False, require_tpu=False,
                        overrides={"config": dict(SMALL_CONFIG),
                                   "traffic": dict(SMALL_TRAFFIC[kind])},
                        log=lambda *a, **k: None)
