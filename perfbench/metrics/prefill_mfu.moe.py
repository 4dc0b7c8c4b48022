"""Prompt processing's share of the chip's roofline for a configuration
of sparse experts held in part, on wall time.

As ``prefill_mfu``, with the work counted by ``perfbench.work_moe.prompt``
(expert rows by the routing's expectation, each held expert read once a
chunk).  None for a configuration without ``moe``.
"""
from perfbench import work, work_moe

UNIT = "%"


def read(run):
    if run.kind != "serve" or "moe" not in run.config:
        return None
    need = wall = 0.0
    for rq in run.requests:
        if rq["emits"]:
            need += work.least_seconds(
                work_moe.prompt(run.config, rq["prompt_len"]), run.peak)
            wall += rq["emits"][0] - rq["submit"]
    return 100.0 * need / wall if wall > 0 else None
