"""Decoding's share of the chip's roofline, on wall time.

Token ``j >= 1`` of a request with a ``P``-token prompt comes from the
decode step at context ``P + j``.  Sum over the window's decode steps of
their least time (``perfbench.work.decode_token``), over the sum of the
gaps between consecutive tokens (host gaps included), in percent.
"""
from perfbench import work

UNIT = "%"


def read(run):
    if run.kind != "serve":
        return None
    need = wall = 0.0
    for rq in run.requests:
        em, P = rq["emits"], rq["prompt_len"]
        for j in range(1, len(em)):
            need += work.least_seconds(
                work.decode_token(run.config, P + j), run.peak)
        if len(em) > 1:
            wall += em[-1] - em[0]
    return 100.0 * need / wall if wall > 0 else None
