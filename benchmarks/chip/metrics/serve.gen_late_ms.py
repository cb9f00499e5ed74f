"""serve.gen_late_ms: 95th percentile of how late each submit returned
after its request was due: the generator's own lag, and the time a submit
waited on the server's lock (host clock)."""
from chipbench.core import percentile


def read(rec):
    return percentile(rec.gen_late_ms, 95) if rec.gen_late_ms else None
