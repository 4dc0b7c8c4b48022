"""Wall time of an instance outside its function bodies (the workflow
runtime: placement, the simulated state tiers, dispatch), per instance,
mean over the window, in ms."""
UNIT = "ms"


def read(run):
    if run.kind != "workflow" or not run.instances:
        return None
    return 1e3 * sum(r["wall_s"] - r["body_s"]
                     for r in run.instances) / len(run.instances)
