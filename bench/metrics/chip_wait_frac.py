"""Share of the traced window in which a chip sat idle after its own
loop of a sweep ended while another chip's loop of that sweep still ran
(``program_trace``'s ``chip_wait``), averaged over the cell's chips; None
on one chip, where nothing waits.  Moves ``sweep_s``."""


def read(run):
    if run.program is None or len(run.program["idle_s"]) < 2:
        return None
    waits = [c.get("chip_wait", 0.0) for c in run.program["idle_s"]]
    return sum(waits) / len(waits) / run.program["window_s"]
