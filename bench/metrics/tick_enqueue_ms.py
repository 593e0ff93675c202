"""Device milliseconds per horizon step under the ``spritz.E_enqueue``
scope (tick phase E) on the busiest chip, from the program's scopes in
the profiler trace (``program_trace.numbers``).  Moves ``sweep_s``."""
import program_trace


def read(run):
    if run.program is None:
        return None
    return program_trace.numbers(run.program)["tick_enqueue_ms"]
