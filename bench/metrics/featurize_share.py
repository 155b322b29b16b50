"""Share of the window the engine's host featurization took
(`EngineStats.featurize_s`: timing sweep and functional probe per chunk,
on the prefetch thread)."""


def read(name, run):
    c = run.counters
    if "featurize_s" not in c or not c.get("window_s"):
        return None
    return 100.0 * c["featurize_s"] / c["window_s"]
