"""Device milliseconds per horizon step under the two policy scopes
(``spritz.policy_feedback``, ``spritz.policy_send``: the ``lax.switch``
over the policy registry) on the busiest chip, from the program's scopes
in the profiler trace (``program_trace.numbers``).  Moves ``sweep_s``."""
import program_trace


def read(run):
    if run.program is None:
        return None
    return program_trace.numbers(run.program)["tick_policy_ms"]
