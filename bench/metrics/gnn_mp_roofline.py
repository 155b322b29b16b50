"""`gnn_mp`'s share of its roofline: the least time the chip could take for
the window's kernel calls, max(operations / bf16 peak, bytes / HBM
bandwidth) summed over calls at their padded shapes (`flops.gnn_mp`),
over the kernel's device time in the trace. The kernel's float32
`Precision.HIGHEST` products take several MXU passes, so the bf16 peak is
an upper bound it cannot reach."""
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import flops  # noqa: E402
import reference  # noqa: E402

# the kernel's own ops: the HLO instruction text starts with its name
KERNEL = re.compile(r"^%gnn_mp(\.\d+)? = ")


def bound(run):
    """(least seconds, compute-bound seconds, memory-bound seconds)."""
    cfg, pk = run.config, run.peaks()
    calls = flops.engine_chunk_calls(int(cfg["eval_chunk"]), int(cfg["n_pad"]),
                                     reference.N_FEAT, int(cfg["hidden"]),
                                     int(cfg["n_layers"]))
    chunks = run.counters.get("chunks", 0)
    comp = sum(f / pk["bf16_flops_per_s"] for f, _ in calls) * chunks
    mem = sum(b / pk["hbm_bytes_per_s"] for _, b in calls) * chunks
    least = sum(max(f / pk["bf16_flops_per_s"], b / pk["hbm_bytes_per_s"])
                for f, b in calls) * chunks
    return least, comp, mem


def read(name, run):
    if not run.counters.get("chunks"):
        return None
    kernel_s = run.trace.op_time(run.devices, run.spans, KERNEL)
    if kernel_s <= 0:
        return None
    return 100.0 * bound(run)[0] / kernel_s
