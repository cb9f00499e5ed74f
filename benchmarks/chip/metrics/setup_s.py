"""setup_s: seconds from the process's start to the window's first timed
call: imports, device check, lowering, compiling or loading compiled
programs, and the cell's warm-up (host clock)."""


def read(rec):
    return rec.setup_s
