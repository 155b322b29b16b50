"""Share of the window the engine spent on its memo: key building and
lookup (``engine.memo``), cache insertion, eviction and row assembly
(``engine.assemble``), on the calling thread (`EngineStats.memo_s`), over
the ``bench.window`` span."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import spans  # noqa: E402


def read(name, run):
    t = spans.threads(run.cell["name"])
    if t is None:
        return None
    return spans.share(t.calling, ("engine.memo", "engine.assemble"),
                       t.window)
