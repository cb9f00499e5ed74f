"""device.busy_ms.batch: milliseconds in which an operation ran on the
device, per launch of the window: the union of the device's op intervals
in the profiler's trace over the launches completed."""


def read(rec):
    if rec.trace is None or not rec.latencies_ms:
        return None
    busy = rec.trace.busy_s()
    return busy * 1e3 / len(rec.latencies_ms) if busy > 0 else None
