"""The functional probe's truth-table gathers against HBM bandwidth: the
least time the window's gathers could take, each table entry read as a
4-byte index and a 4-byte value (`BYTES_PER_READ`) at the chip's HBM
bandwidth, over the device time of the gather operations.

The entries read are the ``lut_reads`` arguments of the workers'
``featurize.probe`` spans that start inside the window (the program counts
them when it traces the labeler, not on the device). The gather operations
are found by the text the trace names them by (`GATHER`). This is a lower
bound of the gathers' time: it counts no table traffic beyond the entries
read, and whatever XLA fuses into a gather counts as gather time. A program
without those spans reports nothing."""
import re
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import spans  # noqa: E402

BYTES_PER_READ = 4 + 4          # int32 index in, int32 table value out

# The trace names a device op by its HLO instruction text, without the
# metadata that carries the program's ``lut_gather`` name scope. On the TPU
# XLA compiles each `apps.lut_gather` into a fusion of kind kCustom that
# reads the stacked table and the clamped index vector:
#   %fusion.10 = s32[131072]{..} fusion(s32[6029312]{..} %constant.96,
#       s32[131072]{..} %broadcast_clamp_fusion.5), kind=kCustom, calls=..
GATHER = re.compile(r"^%[\w.-]+ = s32\[\d+\]\S* fusion\(.*"
                    r"%broadcast_clamp_fusion[\w.-]*\), kind=kCustom")


def entries_read(t) -> int:
    """Table entries the window's probes gathered, by their spans' args."""
    lo, hi = t.window
    return sum(int(args.get("lut_reads", 0))
               for n, s, _, args in spans.on_workers(t)
               if n == "featurize.probe" and lo <= s < hi)


def gather_seconds(devices, window) -> float:
    """Device seconds, inside the window, of the gather operations."""
    lo, hi = window
    return sum((min(e, hi) - max(s, lo)) * 1e-9
               for ops in devices.values() for n, s, e in ops
               if e > lo and s < hi and GATHER.match(n))


def read(name, run):
    t = spans.threads(run.cell["name"])
    if t is None:
        return None
    reads = entries_read(t)
    gather_s = gather_seconds(run.devices, t.window)
    if not reads or gather_s <= 0:
        return None
    least = reads * BYTES_PER_READ / run.peaks()["hbm_bytes_per_s"]
    return 100.0 * least / gather_s
