"""Share of the window the calling thread spent waiting for the prefetch
worker's features: the ``engine.wait_features`` spans of the calling
thread (`EngineStats.feature_wait_s`), over the ``bench.window`` span."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import spans  # noqa: E402


def read(name, run):
    t = spans.threads(run.cell["name"])
    if t is None:
        return None
    return spans.share(t.calling, ("engine.wait_features",), t.window)
