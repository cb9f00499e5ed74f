"""engine.device_rows: rows of the launch's schedule left to run on the
device after plan-time folding (fused rows less folded rows, plus global-
port rows), from the last launch's ``profile()["trace_merge"]["fusion"]``.
Nothing to read unless the megakernel engine ran the launch."""


def read(rec):
    fus = ((rec.profile or {}).get("trace_merge") or {}).get("fusion")
    if not fus:
        return None
    return fus["fused_rows"] - fus["folded_rows"] + fus["gmem_rows"]
