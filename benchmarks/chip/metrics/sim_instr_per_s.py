"""sim_instr_per_s: simulated eGPU instructions completed in the window
over the window's seconds (host clock). An instruction is one block's
sequencer issuing one instruction; the count per launch is the cell's
pinned ``instructions``, so it does not depend on engine or schedule."""


def read(rec):
    if not rec.instructions or not rec.window_s > 0:
        return None
    return rec.instructions / rec.window_s
