"""Device-idle milliseconds inside the benchmark's ``step`` spans
(``SensorFleetEngine.step``: batch assembly, the kernel call, the blocking
copy back and the per-slot bookkeeping), per engine step of the traced
stretch."""


def read(m):
    if not m["steps"]:
        return None
    return m["trace"]["idle_s_by_span"].get("step", 0.0) * 1e3 / m["steps"]
