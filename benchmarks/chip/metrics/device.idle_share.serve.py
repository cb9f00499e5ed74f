"""The device's idle share of the traced window, in percent: 100 times one
less the union of its op intervals over the window's length."""


def read(rec):
    if rec.trace is None or not rec.trace.window_s() > 0:
        return None
    return 100.0 * (1.0 - rec.trace.busy_s() / rec.trace.window_s())
