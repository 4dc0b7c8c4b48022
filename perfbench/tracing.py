"""Device trace: capture a short window and reduce it to numbers.

``capture`` runs a callable under the JAX profiler, inside a host
annotation named ``WINDOW``, and returns ``reduce`` of what the profiler
wrote.  ``reduce`` works on ``jax.profiler.ProfileData``:

- the window is the ``WINDOW`` annotation's span on the host;
- a device's busy time is the union of its ``XLA Ops`` events inside the
  window (planes ``/device:TPU:<n>``), averaged over the chips;
- the top device operations are the summed time of the innermost
  operations (a loop's own event, which encloses its body's, is left
  out), named by their HLO text without layouts;
- each idle gap of at least ``GAP_MIN_NS`` is put down to the innermost
  host event on the window's thread that covers the gap's midpoint;
  shorter gaps are summed under ``SHORT_GAPS``.

``to_text_proto`` writes events back as a text XSpace, which is how a
small recorded trace is kept for the tests.
"""
from __future__ import annotations

import bisect
import re
import shutil
import tempfile
from collections import defaultdict
from typing import Callable, Dict, Iterable, List, Tuple

WINDOW = "bench.window"
DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
GAP_MIN_NS = 10_000
SHORT_GAPS = "(idle gaps under 10 us)"
TOP = 10
NAME_CHARS = 120

Interval = Tuple[float, float]


def capture(segment: Callable[[], None]) -> dict:
    """Trace ``segment`` and reduce the trace; the files are removed."""
    import jax
    logdir = tempfile.mkdtemp(prefix="perfbench-trace-")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(WINDOW):
                segment()
        finally:
            jax.profiler.stop_trace()
        return reduce(jax.profiler.ProfileData.from_file(_xplane(logdir)))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def _xplane(logdir: str) -> str:
    from pathlib import Path
    found = sorted(Path(logdir).rglob("*.xplane.pb"))
    if not found:
        raise RuntimeError(f"the profiler wrote no .xplane.pb under {logdir}")
    return str(found[-1])


def _union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def _clip(s: float, e: float, lo: float, hi: float):
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _short(name: str) -> str:
    while True:
        bare = re.sub(r"\{[^{}]*\}", "", name)
        if bare == name:
            return name[:NAME_CHARS]
        name = bare


def _leaves(ops: List[Tuple[str, float, float]]):
    """The events that enclose no other event of the line."""
    ops = sorted(ops, key=lambda x: (x[1], -x[2]))
    parent = [False] * len(ops)
    open_: List[int] = []
    for i, (_, s, e) in enumerate(ops):
        while open_ and ops[open_[-1]][2] <= s:
            open_.pop()
        if open_ and ops[open_[-1]][2] >= e:
            parent[open_[-1]] = True
        open_.append(i)
    return [op for op, p in zip(ops, parent) if not p]


def _events(line) -> List[Tuple[str, float, float]]:
    return [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
            for ev in line.events]


def _window_line(planes):
    """(window start, end, host events of the window's thread)."""
    for plane in planes:
        for line in plane.lines:
            evs = _events(line)
            for name, s, e in evs:
                if name == WINDOW:
                    return s, e, sorted(evs, key=lambda x: (x[1], -x[2]))
    raise RuntimeError(f"no {WINDOW!r} annotation in the trace")


def _innermost(host, starts, t: float) -> str:
    """Name of the latest-starting host event that covers ``t``."""
    i = bisect.bisect_right(starts, t)
    for name, s, e in reversed(host[max(0, i - 4096):i]):
        if e >= t:
            return name
    return WINDOW


def reduce(pd) -> Dict:
    planes = list(pd.planes)
    lo, hi, host = _window_line(planes)
    host = [h for h in host if h[0] != WINDOW]
    starts = [h[1] for h in host]
    chips = [p for p in planes if p.name.startswith(DEVICE_PREFIX)]
    if not chips:
        raise RuntimeError("no TPU device plane in the trace")
    op_time: Dict[str, float] = defaultdict(float)
    gap_time: Dict[str, float] = defaultdict(float)
    busy_total = 0.0
    for chip in chips:
        ops = []
        for line in chip.lines:
            if line.name == OPS_LINE:
                ops += [(n,) + c for n, s, e in _events(line)
                        for c in [_clip(s, e, lo, hi)] if c]
        for name, s, e in _leaves(ops):
            op_time[_short(name)] += e - s
        busy = _union((s, e) for _, s, e in ops)
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [t for iv in busy for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e - s >= GAP_MIN_NS:
                gap_time[_innermost(host, starts, (s + e) / 2)] += e - s
            elif e > s:
                gap_time[SHORT_GAPS] += e - s
    n = len(chips)
    window_s = (hi - lo) * 1e-9
    busy_s = busy_total / n * 1e-9

    def top(d):
        return [[k, v / n * 1e-9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]

    return {"window_s": window_s, "busy_s": busy_s, "chips": n,
            "idle_pct": 100.0 * (1.0 - busy_s / window_s),
            "device_ops": top(op_time), "idle_gaps": top(gap_time)}


# ---------------------------------------------------------------------------
# a small trace kept as text
# ---------------------------------------------------------------------------
def to_text_proto(planes: Dict[str, Dict[str, List[Tuple[str, int, int]]]]
                  ) -> str:
    """``{plane: {line: [(name, start_ns, end_ns), ...]}}`` as an XSpace
    text proto that ``ProfileData.from_text_proto`` reads back."""
    out = []
    for pid, (pname, lines) in enumerate(planes.items(), 1):
        names = sorted({n for evs in lines.values() for n, _, _ in evs})
        mid = {n: i for i, n in enumerate(names, 1)}
        out.append(f"planes {{\n  id: {pid}\n  name: {_q(pname)}")
        for lid, (lname, evs) in enumerate(lines.items(), 1):
            base = min((s for _, s, _ in evs), default=0)
            out.append(f"  lines {{\n    id: {lid}\n    name: {_q(lname)}"
                       f"\n    timestamp_ns: {int(base)}")
            for n, s, e in evs:
                out.append(f"    events {{ metadata_id: {mid[n]} "
                           f"offset_ps: {int(s - base) * 1000} "
                           f"duration_ps: {int(e - s) * 1000} }}")
            out.append("  }")
        for n, i in mid.items():
            out.append(f"  event_metadata {{ key: {i} value {{ id: {i} "
                       f"name: {_q(n)} }} }}")
        out.append("}")
    return "\n".join(out) + "\n"


def _q(s: str) -> str:
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'

