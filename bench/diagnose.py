"""Whether the program's functional models read the same on the chip as on
the host's CPU, for one configuration.

    python3 bench/diagnose.py --config kmeans-paper --configs 4096

Prints one JSON object:

* ``ilog2_bad``: operands below 2**21 at which the program's unit helper
  ``floor(log2(x))`` differs from the integer answer on this device;
* ``lut_bad``: per library entry of a LUT kind, the truth-table entries
  the program builds on this device that differ from the same unit
  function evaluated on the CPU;
* ``probe_bad``: of ``--configs`` configurations drawn from ``--seed``,
  those whose functional-probe columns (the program's, on this device)
  differ by more than 1e-6 from the reference's (on the CPU), with the
  LUT entries they use most;
* ``row_gap_q``: quantiles of the engine's row gap against the reference
  on the first 1,024 of them, and how many of the rows over 1e-5 are
  rows whose probe differs.

Needs the chip, like ``run.py``; the benchmark's runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
from collections import Counter
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--configs", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=101)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(run.CACHE)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        out = {"device": run.device_info(1)["kind"]}
    except run.NoChip as e:
        print(f"diagnose: {e}", file=sys.stderr)
        return 2
    cfg = run.load_json(run.BENCH / "configs" / f"{args.config}.json")
    program = run.import_program()
    import jax
    import jax.numpy as jnp
    import numpy as np

    import reference as ref
    from surrogate import Surrogate, draw_configs
    from repro.accel import batch_oracle, library, units
    cpu = jax.devices("cpu")[0]

    x = np.arange(1, 1 << 21, dtype=np.int32)
    exact = (np.frexp(x.astype(np.float64))[1] - 1).astype(np.int32)
    got = np.asarray(jax.jit(units._ilog2)(jnp.asarray(x)))
    out["ilog2_bad"] = x[got != exact][:16].tolist()

    s = Surrogate(cfg, program)
    lut_bad = {}
    for kind in sorted({n.kind for n in s.app.unit_nodes}):
        if kind not in library.LUT_DOMAINS:
            continue
        ea, eb = library.lut_domain(s.app.name, kind)
        for e in s.entries[kind]:
            dev = np.asarray(e.inst.lut(ea, eb))
            with jax.default_device(cpu):
                host = np.asarray(e.inst.lut(ea, eb))
            if (dev != host).any():
                lut_bad[e.inst.name] = int((dev != host).sum())
    out["lut_bad"] = lut_bad

    cfgs = draw_configs(np.random.default_rng([args.seed, 4]), s.sizes,
                        args.configs, set())
    probe = batch_oracle.probe_batch(s.app, s.entries, cfgs)
    with ref.host():
        want = [1.0 - s.acc.accuracy_batch(cfgs, jax.device_put(i, cpu),
                                           jax.device_put(e, cpu))
                for i, e in s.acc.probes]
    diff = np.maximum(np.abs(probe["probe_err8"] - want[0]),
                      np.abs(probe["probe_err16"] - want[1]))
    bad = np.where(diff > 1e-6)[0]
    used = Counter(s.entries[n.kind][cfgs[i, j]].inst.name
                   for i in bad for j, n in enumerate(s.app.unit_nodes)
                   if n.kind in library.LUT_DOMAINS)
    out["probe_bad"] = {"configs": int(len(bad)), "of": args.configs,
                        "max_diff": float(diff.max()),
                        "lut_entries_used": dict(used.most_common(8))}

    n = min(1024, args.configs)
    rows = s.engine(cfgs[:n])
    with ref.host():
        gaps = s.gaps(cfgs[:n], rows)
    big = set(np.where(gaps > 1e-5)[0].tolist())
    out["row_gap_q"] = {"q50": float(np.quantile(gaps, 0.5)),
                        "q99": float(np.quantile(gaps, 0.99)),
                        "max": float(gaps.max()), "over_1e-5": len(big),
                        "over_1e-5_with_probe_bad":
                            len(big & set(bad.tolist()))}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
