"""Decoding's share of the chip's roofline for a configuration of sparse
experts held in part, on wall time.

As ``decode_mfu``, with the work counted by
``perfbench.work_moe.decode_token`` (a token's expected held rows, each
one expert's weights read).  None for a configuration without ``moe``.
"""
from perfbench import work, work_moe

UNIT = "%"


def read(run):
    if run.kind != "serve" or "moe" not in run.config:
        return None
    need = wall = 0.0
    for rq in run.requests:
        em, P = rq["emits"], rq["prompt_len"]
        for j in range(1, len(em)):
            need += work.least_seconds(
                work_moe.decode_token(run.config, P + j), run.peak)
        if len(em) > 1:
            wall += em[-1] - em[0]
    return 100.0 * need / wall if wall > 0 else None
