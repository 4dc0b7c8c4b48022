"""Share of the traced serving window in which no operation ran on the
device: 1 - (union of device-op intervals) / window, in percent
(``perfbench.tracing.reduce``).  The traced segment is the mix's next
requests after the window; where it opens with a long prompt, as
``azure-conv`` does, it is the idle share of prompt feeding."""
UNIT = "%"


def read(run):
    if run.kind != "serve" or run.trace is None:
        return None
    return run.trace["idle_pct"]
