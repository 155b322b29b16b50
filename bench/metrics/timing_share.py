"""Share of the window the featurizer spent in its float64 timing sweep
(`batch_oracle.timing_batch`): the ``featurize.timing`` spans of the
prefetch workers (`EngineStats.timing_s`), over the ``bench.window``
span."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import spans  # noqa: E402


def read(name, run):
    t = spans.threads(run.cell["name"])
    if t is None:
        return None
    return spans.share(spans.on_workers(t), ("featurize.timing",), t.window)
