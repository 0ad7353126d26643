"""99th percentile, in milliseconds, of the wait from a request's due time
on the arrival schedule to its slot claim, over the requests due in the
window that got a slot (read after each ``IngestQueue.pump``)."""

from bench.loop import percentile


def read(m):
    waits = m["queue_wait_s"]
    if not waits:
        return None
    return percentile(waits, 99) * 1e3
