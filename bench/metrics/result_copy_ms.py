"""Host milliseconds per sweep of the program's ``spritz.results`` span
(the per-lane result copies) after the last chip's loop ended, the mean
over the traced sweeps (``program_trace.numbers``).  Moves ``sweep_s``."""
import program_trace


def read(run):
    if run.program is None:
        return None
    return program_trace.numbers(run.program)["result_copy_ms"]
