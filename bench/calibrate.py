"""Readings that the cells' limits are set from, many seeds in one process.

    python3 bench/calibrate.py --workload sobel.wave --seeds 101-112 \
        --seconds 3 --control 101-103

The cell is built once; for each seed its inputs are drawn, its window
runs at the cell's own load for ``--seconds``, and the program's numbers
are read as a run reads them.
For the ``--control`` seeds the control is read too: the plain reference,
computed one precision below what the configuration states, in the
program's place. Each reading is one JSON line on standard output, and the
last line gathers, per number, the largest program reading (the lower end
of a limit) and the smallest control reading (the upper end). The
benchmark's own runs never run the control. Needs the chip, like
``run.py``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import reference as ref  # noqa: E402
import run  # noqa: E402


def seeds(text: str):
    out = []
    for part in text.split(","):
        if "-" in part:
            a, b = part.split("-")
            out += list(range(int(a), int(b) + 1))
        elif part:
            out.append(int(part))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--control", default="")
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    spec = run.load_json(run.ROOT / "BENCHMARK.json")
    r = run.resolve(spec, args.workload)
    try:
        run.device_info(int(r["cell"]["chips"]))
    except run.NoChip as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 2
    program = run.import_program()
    control = set(seeds(args.control))
    lower, upper = {}, {}
    todo = seeds(args.seeds)
    drv = run.loop(r["traffic"]["kind"])(r["config"], r["traffic"], todo[0],
                                         args.seconds, program)
    drv.build()
    for sd in todo:
        t0 = time.perf_counter()
        drv.seed = sd
        drv.prepare()
        drv.window()
        got, failed = drv.check()
        line = {"seed": sd, "program": got, "failed": failed, "e2e": drv.e2e()}
        for k, v in got.items():
            lower[k] = max(lower.get(k, v), v)
        if sd in control:
            low, _ = drv.check(control=True)
            line["control"] = low
            for k, v in low.items():
                upper[k] = min(upper.get(k, v), v)
            with ref.host():
                rows = drv.s.control_gaps(drv.checked, "high")
            line["control_written_out_row_gap"] = float(rows.max())
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    print(json.dumps({"workload": args.workload, "lower": lower,
                      "upper": upper}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
