"""Device-idle milliseconds inside the benchmark's ``pump`` spans
(``IngestQueue.pump``: slot claims and their state writes), per engine step
of the traced stretch."""


def read(m):
    if not m["steps"]:
        return None
    return m["trace"]["idle_s_by_span"].get("pump", 0.0) * 1e3 / m["steps"]
