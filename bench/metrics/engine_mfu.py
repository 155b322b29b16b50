"""Useful forward operations per second as a share of the chip's bf16
peak: the two-stage forward of one configuration on its real nodes
(`flops.forward_flops`) times the window's configurations per second.
The forward runs in float32 at `Precision.HIGHEST`, several MXU passes per
product, so the bf16 peak is an upper bound it cannot reach."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import flops  # noqa: E402
import reference  # noqa: E402


def read(name, run):
    rate = run.e2e.get("configs_per_s")
    if not rate:
        return None
    cfg = run.config
    nodes = len(reference.Accelerator(cfg).gnodes)
    per = flops.forward_flops(nodes, reference.N_FEAT, int(cfg["hidden"]),
                              int(cfg["n_layers"]))
    return 100.0 * per * rate / run.peaks()["bf16_flops_per_s"]
