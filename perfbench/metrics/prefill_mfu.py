"""Prompt processing's share of the chip's roofline, on wall time.

Sum over the window's requests of the least time their prompts need
(``perfbench.work.prompt``), over the sum of their prompt wall times
(submit to first token, host gaps included), in percent.
"""
from perfbench import work

UNIT = "%"


def read(run):
    if run.kind != "serve":
        return None
    need = wall = 0.0
    for rq in run.requests:
        if rq["emits"]:
            need += work.least_seconds(
                work.prompt(run.config, rq["prompt_len"]), run.peak)
            wall += rq["emits"][0] - rq["submit"]
    return 100.0 * need / wall if wall > 0 else None
