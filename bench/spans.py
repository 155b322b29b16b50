"""The program's own spans (``engine.*``, ``featurize.*``) in a cell's newest
trace, thread by thread, and the shares of the window they take.

`SurrogateEngine` opens a span at each of its layer boundaries
(`EngineStats.span`). In a trace of the ``wave`` loop, the calling thread
is the host line that holds ``bench.window``; the engine starts a prefetch
thread per call, so every other line with ``featurize.*`` spans is a
worker. A trace of a program without these spans gives empty lists, and
the readers then report nothing.
"""
from __future__ import annotations

import functools
import glob
import os
from pathlib import Path
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, Tuple

ROOT = Path(__file__).resolve().parent.parent
TRACES = ROOT / "bench_out" / "trace"
WINDOW = "bench.window"
PREFIXES = ("engine.", "featurize.")

Span = Tuple[str, int, int, Dict]     # name, start ns, end ns, args


class Threads(NamedTuple):
    window: Tuple[int, int]           # the bench.window span, ns
    calling: List[Span]               # program spans of the calling thread
    workers: List[List[Span]]         # one list per worker line


def split(lines: Sequence[Sequence[Span]]) -> Optional[Threads]:
    """The calling thread and the workers among a trace's host lines
    (each a list of spans, ``bench.window`` among them); None without the
    window span."""
    for i, line in enumerate(lines):
        win = [(s, e) for n, s, e, _ in line if n == WINDOW]
        if win:
            break
    else:
        return None
    own = [[sp for sp in line if sp[0].startswith(PREFIXES)]
           for line in lines]
    workers = [ln for j, ln in enumerate(own) if j != i
               and any(sp[0].startswith("featurize.") for sp in ln)]
    return Threads((min(s for s, _ in win), max(e for _, e in win)),
                   own[i], workers)


@functools.lru_cache(maxsize=4)
def load(path: str) -> Optional[Threads]:
    """`split` of one ``.xplane.pb`` file's host lines, read once."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    lines = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            lines.extend(
                [(e.name, int(e.start_ns), int(e.start_ns + e.duration_ns),
                  dict(e.stats)) for e in line.events
                 if e.name == WINDOW or e.name.startswith(PREFIXES)]
                for line in plane.lines)
    return split(lines)


def threads(cell: str) -> Optional[Threads]:
    """The spans of the newest trace of `cell` (``run.py --trace 1``
    writes it under ``TRACES/<cell>``); None without a trace."""
    files = glob.glob(os.path.join(TRACES, cell, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    return load(max(files, key=os.path.getmtime))


def intervals(spans: Iterable[Span], names: Sequence[str],
              window: Tuple[int, int]) -> List[Tuple[int, int]]:
    """The spans named in `names`, clipped to the window."""
    lo, hi = window
    return [(max(s, lo), min(e, hi)) for n, s, e, _ in spans
            if n in names and e > lo and s < hi]


def share(spans: Iterable[Span], names: Sequence[str],
          window: Tuple[int, int]) -> Optional[float]:
    """Percent of the window in the spans named in `names` (summed, so
    spans of several threads add up); None when there are none."""
    iv = intervals(spans, names, window)
    if not iv:
        return None
    return 100.0 * sum(e - s for s, e in iv) / (window[1] - window[0])


def on_workers(t: Threads) -> List[Span]:
    return [sp for w in t.workers for sp in w]


def overlap(a: Sequence[Tuple[int, int]],
            b: Sequence[Tuple[int, int]]) -> int:
    """Length of the intersection of two sorted, disjoint interval lists."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total
