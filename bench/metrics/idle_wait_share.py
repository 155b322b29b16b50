"""Share of the window in which the first TPU ran no operation while the
calling thread waited for features: the union of that device's operations
(clipped to the window) against the calling thread's
``engine.wait_features`` spans, over the ``bench.window`` span. The device
and host planes share the profiler's clock, as `trace.py` assumes."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import spans  # noqa: E402


def read(name, run):
    t = spans.threads(run.cell["name"])
    if t is None:
        return None
    union = run.trace.union
    wait = union(spans.intervals(t.calling, ("engine.wait_features",),
                                 t.window))
    lo, hi = t.window
    for dev in sorted(run.devices):
        busy = union([(max(s, lo), min(e, hi)) for _, s, e in run.devices[dev]
                      if e > lo and s < hi])
        if busy:
            break
    else:
        return None
    if not wait:
        return None
    idle_wait = sum(e - s for s, e in wait) - spans.overlap(wait, busy)
    return 100.0 * idle_wait / (hi - lo)
