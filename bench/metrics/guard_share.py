"""Share of the window the engine spent reading the functional probe's
LUT-domain guards: the calling thread's ``engine.guards`` spans, inside
``engine.collect`` (`EngineStats.guard_s`), over the ``bench.window``
span. A program that opens no such span reports nothing."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import spans  # noqa: E402


def read(name, run):
    t = spans.threads(run.cell["name"])
    if t is None:
        return None
    return spans.share(t.calling, ("engine.guards",), t.window)
