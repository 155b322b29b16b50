"""Share of the window the engine spent blocked collecting rows
(`EngineStats.collect_s`: device to host copy and denormalization; device
compute the pipeline did not hide shows here)."""


def read(name, run):
    c = run.counters
    if "collect_s" not in c or not c.get("window_s"):
        return None
    return 100.0 * c["collect_s"] / c["window_s"]
