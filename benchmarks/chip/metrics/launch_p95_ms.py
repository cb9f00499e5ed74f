"""launch_p95_ms: 95th percentile of the caller-side latency of every
launch in the window, each until its results are on the host (host
clock)."""
from chipbench.core import percentile


def read(rec):
    return percentile(rec.latencies_ms, 95) if rec.latencies_ms else None
