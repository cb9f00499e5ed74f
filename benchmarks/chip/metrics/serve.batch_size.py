"""serve.batch_size: mean number of requests in the merged launch that
served each request of the window (``ServeResult.batch_size``, a count of
the front door)."""


def read(rec):
    if not rec.batch_sizes:
        return None
    return sum(rec.batch_sizes) / len(rec.batch_sizes)
