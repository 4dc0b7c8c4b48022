"""Wall time inside the four function bodies, per instance, mean over
the window, in ms.  In the traced run each body's span ends on
``block_until_ready`` of its output."""
UNIT = "ms"


def read(run):
    if run.kind != "workflow" or not run.instances:
        return None
    return 1e3 * sum(r["body_s"] for r in run.instances) / len(run.instances)
