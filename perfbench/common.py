"""What every runner shares: the benchmark's files, the device, the clock,
percentiles, the compile counter and the result line.

Everything here is found by name from ``BENCHMARK.json``: a cell names a
configuration (``perfbench/configs/<name>.json``) and a traffic mix
(``perfbench/traffic/<name>.json``); the configuration names its runner
(``perfbench/runners/<runner>.py``) and its plain reference
(``perfbench/refs/<reference>.py``); each per-layer metric is read by
``perfbench/metrics/<metric>.py``.  Adding a cell, a configuration or a
metric therefore adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence

BENCH_DIR = Path(__file__).resolve().parent
CHECKOUT = BENCH_DIR.parent
SRC = CHECKOUT / "src"


class BenchError(RuntimeError):
    """A run that cannot produce a result: no chip, missing file, bad cell."""


# ---------------------------------------------------------------------------
# files found by name
# ---------------------------------------------------------------------------
def load_benchmark(path: Path = CHECKOUT / "BENCHMARK.json") -> dict:
    if not path.is_file():
        raise BenchError(f"{path} not found")
    return json.loads(path.read_text())


def find(entries: Sequence[dict], name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise BenchError(f"no {what} named {name!r} in BENCHMARK.json")


def read_json(kind: str, name: str) -> dict:
    path = BENCH_DIR / kind / f"{name}.json"
    if not path.is_file():
        raise BenchError(f"{path} not found")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """Import ``perfbench/<kind>/<name>.py``; names may hold dots."""
    path = BENCH_DIR / kind / f"{name}.py"
    if not path.is_file():
        raise BenchError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(
        f"perfbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """Peak FLOP/s and bytes/s of one chip, keyed by ``device_kind``."""
    table = json.loads((BENCH_DIR / "peaks.json").read_text())["devices"]
    if device_kind not in table:
        raise BenchError(f"device kind {device_kind!r} is not in "
                         f"perfbench/peaks.json ({sorted(table)})")
    return table[device_kind]


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------
def require_tpu(chips: int):
    """The devices JAX sees, or BenchError where they are not ``chips``
    TPUs.  There is no fallback to another platform."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise BenchError(f"no TPU found: JAX sees {devs[0].platform!r}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    return devs


def device_info(devs) -> dict:
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": int(peak)}


def seed_words(seed: int) -> tuple:
    """Two 32-bit words of a seed that may exceed 32 bits."""
    if seed < 0:
        raise BenchError(f"seed must be >= 0, got {seed}")
    return seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF


def prng_key(seed: int, salt: int = 0):
    import jax
    lo, hi = seed_words(seed)
    key = jax.random.PRNGKey(lo)
    return jax.random.fold_in(jax.random.fold_in(key, hi), salt)


# ---------------------------------------------------------------------------
# statistics (the arithmetic of repro.sim.metrics.percentile, copied so
# that no PR to the program can change how the benchmark counts)
# ---------------------------------------------------------------------------
def percentile(xs: Sequence[float], p: float) -> float:
    """Linear-interpolated percentile (p in [0, 100]); 0.0 on empty."""
    xs = sorted(xs)
    n = len(xs)
    if n == 0:
        return 0.0
    if n == 1:
        return float(xs[0])
    rank = (p / 100.0) * (n - 1)
    lo = int(rank)
    hi = min(lo + 1, n - 1)
    frac = rank - lo
    return float(xs[lo]) * (1.0 - frac) + float(xs[hi]) * frac


# ---------------------------------------------------------------------------
# compiles inside the window
# ---------------------------------------------------------------------------
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Counts programs compiled or loaded from the cache while on."""

    def __init__(self):
        import jax
        self.on = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kwargs):
        if self.on and event == BACKEND_COMPILE_EVENT:
            self.count += 1


# ---------------------------------------------------------------------------
# checks and the result line
# ---------------------------------------------------------------------------
class Checks:
    """Numbers compared with their limits; a number above its limit, or
    one that is not finite, makes the run not correct."""

    def __init__(self):
        self.items: Dict[str, dict] = {}

    def add(self, name: str, value: float, limit: float):
        self.items[name] = {"value": float(value), "limit": float(limit)}

    @property
    def correct(self) -> bool:
        return bool(self.items) and all(
            v["value"] == v["value"] and v["value"] <= v["limit"]
            for v in self.items.values())

    def lines(self) -> List[str]:
        return [f"check {k}: {v['value']!r} (limit {v['limit']!r})"
                for k, v in self.items.items()]


def metric(value: float, unit: str) -> dict:
    return {"value": float(value), "unit": unit}


def emit_result(correct: bool, attempted: int, failed: int, metrics: dict,
                device: dict, checks: Checks,
                breakdown: Optional[dict] = None):
    """The last stdout line, then the checks as the last stderr lines."""
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed), "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = checks.items
    sys.stdout.write(json.dumps(out) + "\n")
    sys.stdout.flush()
    for line in checks.lines():
        sys.stderr.write(line + "\n")
    sys.stderr.flush()


def clock() -> float:
    return time.perf_counter()


def note(text: str):
    """A line on stderr, ahead of the result."""
    sys.stderr.write(f"perfbench: {text}\n")
    sys.stderr.flush()


def read_layer_metrics(names: Sequence[str], run) -> dict:
    """Each per-layer metric from its own reader; a reader that finds
    nothing returns None and the metric is left out."""
    out = {}
    for name in names:
        reader = load_module("metrics", name)
        got = reader.read(run)
        if got is not None:
            out[name] = metric(got, reader.UNIT)
    return out


def cell_metric_names(bench: dict, cell: str, section: str) -> List[str]:
    """Names of the ``section`` metrics that ``cell`` reports."""
    return [m["name"] for m in bench[section]
            if cell in m.get("workloads", [cell])]

