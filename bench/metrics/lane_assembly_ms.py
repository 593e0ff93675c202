"""Host milliseconds per sweep in the program's ``spritz.assemble_lanes``
span (``run_batch``'s lane assembly), the mean over the traced sweeps
(``program_trace.numbers``).  Moves ``sweep_s``."""
import program_trace


def read(run):
    if run.program is None:
        return None
    return program_trace.numbers(run.program)["lane_assembly_ms"]
