"""Share of the traced workflow window in which no operation ran on the
device: 1 - (union of device-op intervals) / window, in percent
(``perfbench.tracing.reduce``)."""
UNIT = "%"


def read(run):
    if run.kind != "workflow" or run.trace is None:
        return None
    return run.trace["idle_pct"]
