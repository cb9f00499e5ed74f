"""request_p95_ms: 95th percentile of the latency of every request due in
the window, from when it was due to when its answer was on the host; a
failed request counts with the time the run waited for it (host
clock)."""
from chipbench.core import percentile


def read(rec):
    return percentile(rec.latencies_ms, 95) if rec.latencies_ms else None
