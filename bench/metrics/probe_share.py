"""Share of the window the featurizer spent in its functional probe
(`batch_oracle.probe_batch`, a blocking jitted call): the
``featurize.probe`` spans of the prefetch workers (`EngineStats.probe_s`),
over the ``bench.window`` span."""
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import spans  # noqa: E402


def read(name, run):
    t = spans.threads(run.cell["name"])
    if t is None:
        return None
    return spans.share(spans.on_workers(t), ("featurize.probe",), t.window)
