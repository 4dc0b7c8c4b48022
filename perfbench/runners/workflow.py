"""Workflow cells: one closed-loop client through ``WorkflowEngine``.

Set-up makes the mix's pool of inputs from the seed on the device and
hands them in by replacing ``repro.serverless.engine.make_payload``, so
the engine's own fixed-seed input stays out of the window, and the input
is on the chip when the chain starts, as Databelt places it.  Two
instances run before the window, which compiles every body's operations.

The window: instances run one after another (simulated start ``i * 90``
s, as far apart as the smoke runs them) until ``seconds`` have passed.
An instance's wall time stops once every body output it made is ready.

With ``trace`` on, each body runs inside a host annotation and its span
ends on ``block_until_ready`` of its output; the input's arrival and the
whole instance are annotated too.  After the window a further
``trace_seconds`` of instances run under the profiler.

Then the check: the instances drawn from the seed are replayed through
the plain reference from their own input, and what each body was handed
and returned is compared with it (see ``check``).
"""
from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from perfbench import common, traffic, tracing

SIM_GAP_S = 90.0
WARMUP = 2


@dataclasses.dataclass
class FloodRun:
    """What a window leaves for the metric readers."""
    kind: str
    config: Dict
    peak: Optional[Dict]
    instances: List[dict]
    trace: Optional[Dict] = None


class InstanceRunner:
    """Builds and runs instances; keeps what the check needs."""

    def __init__(self, config: Dict, mix: Dict, seed: int, annotate: bool):
        import repro.serverless.engine as engine_mod
        from repro.continuum.network import ContinuumNetwork
        from repro.continuum.orbits import Constellation
        from repro.serverless.engine import WorkflowEngine
        self.config, self.annotate = config, annotate
        self.pool = traffic.workflow_payloads(mix, config, seed)
        h, w = config["frame_hw"]
        self.input_bytes = float(config["frames"] * h * w * 4)
        self.sampler = traffic.Sampler(seed, mix["check_every"],
                                       mix["check_cap"])
        self.current: Optional[dict] = None
        engine_mod.make_payload = self._payload
        net = ContinuumNetwork(Constellation(**config["network"]))
        self.eng = WorkflowEngine(
            net, strategy=config["strategy"],
            fusion_depth=config["fusion_depth"], real_compute=True,
            seed=common.seed_words(seed)[0] & 0x7FFFFFFF)
        self.i = 0
        self.sampling = False
        self.kept: List[dict] = []

    def _span(self, name: str):
        return jax.profiler.TraceAnnotation(name) if self.annotate \
            else nullcontext()

    def _payload(self, size_bytes, with_sar: bool = True):
        with self._span("flood.input"):
            if size_bytes != self.input_bytes:
                raise ValueError(f"engine asked for {size_bytes} bytes, the "
                                 f"configuration states {self.input_bytes}")
            return dict(self.current["input"])

    def _wrap(self, wf, rec: dict):
        for fn in wf.functions:
            body = fn.compute

            def timed(payload, body=body, name=fn.name):
                t = common.clock()
                with self._span(f"flood.body.{name}"):
                    out = body(payload)
                    if self.annotate:
                        jax.block_until_ready(out)
                rec["body_s"] += common.clock() - t
                rec["outputs"].append(out)
                if rec["kept"]:
                    # ingest's masked frames are not compared, and would
                    # hold a whole input per kept instance
                    rec["io"][name] = {k: v for k, v in out.items()
                                       if k != "frames"}
                return out
            fn.compute = timed
        return wf

    def instance(self) -> dict:
        """Run the next instance to the end; its record."""
        from repro.serverless.workflow import flood_workflow
        i = self.i
        self.i += 1
        rec = {"i": i, "body_s": 0.0, "outputs": [], "io": {},
               "kept": self.sampling and self.sampler(),
               "input": self.pool[i % len(self.pool)]}
        self.current = rec
        wf = self._wrap(flood_workflow(f"bench-{i}"), rec)
        t = common.clock()
        with self._span("flood.instance"):
            self.eng.run_instance(wf, self.input_bytes, t0=i * SIM_GAP_S)
            jax.block_until_ready(rec["outputs"])
        rec["wall_s"] = common.clock() - t
        rec["bodies"] = len(rec["outputs"])
        del rec["outputs"]
        if rec["kept"]:
            self.kept.append(rec)
        else:
            rec["io"] = {}
        return rec


def run(config: Dict, mix: Dict, seed: int, seconds: float, trace: bool,
        devs, t_process: float, ref, peak: Optional[Dict] = None) -> dict:
    runner = InstanceRunner(config, mix, seed, annotate=trace)
    jax.block_until_ready(runner.pool)
    common.note(f"set-up: inputs made at {common.clock() - t_process:.3f} s")
    runner.i = -WARMUP
    for _ in range(WARMUP):
        runner.instance()
    compiles = common.CompileCounter()

    records = []
    runner.sampling = compiles.on = True
    t0 = common.clock()
    setup_s = t0 - t_process
    common.note(f"set-up: warm at {setup_s:.3f} s")
    deadline = t0 + seconds
    while common.clock() < deadline:
        records.append(runner.instance())
    runner.sampling = compiles.on = False
    in_compiles = compiles.count

    trace_red = None
    if trace:
        def segment():
            stop = common.clock() + mix["trace_seconds"]
            while common.clock() < stop:
                runner.instance()
        trace_red = tracing.capture(segment)

    device = common.device_info(devs)
    checks = check(runner.kept, config, ref)
    checks.add("compiles_in_window", in_compiles, 0)
    checks.add("missing_bodies", sum(
        r["bodies"] != config["functions"] for r in records), 0)

    inst = [{"wall_s": r["wall_s"], "body_s": r["body_s"]} for r in records]
    e2e = {"instance_p95_ms": common.metric(
               1e3 * common.percentile([r["wall_s"] for r in inst], 95),
               "ms"),
           "setup_s": common.metric(setup_s, "s")}
    failed = sum(r["bodies"] != config["functions"] for r in records)
    return {"e2e": e2e, "checks": checks, "device": device,
            "attempted": len(records), "failed": failed,
            "run": FloodRun("workflow", config, peak, inst, trace_red),
            "kept": runner.kept}


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
NUMBERS = ("keep_flips", "score_err")


def readings(rec: dict, ref, dtype=jnp.float32,
             memo: Optional[dict] = None) -> Dict[str, float]:
    """One instance against the reference chain from its own input.

    - ``keep_flips``: frames ingest kept or dropped unlike the reference;
    - ``score_err``: |alarm score - reference score|.  The score is made
      from the detections and the flood map that the engine handed
      through detect, map and alarm, so it also checks the state passed
      between bodies.
    With ``dtype`` below float32 the reference in that precision is read
    in the program's place (the control).  Detections, flood map and
    frames are not compared: the control moves them no more than the
    program's own rounding does (PERF.md).  ``memo`` keeps the
    reference's answers by input, which instances share."""
    memo = {} if memo is None else memo
    frames = rec["input"]["frames"]

    def chain(dt):
        key = (id(frames), jnp.dtype(dt).name)
        if key not in memo:
            memo[key] = ref.chain(jnp.asarray(frames), dtype=dt)
        return memo[key]

    want = chain(jnp.float32)
    io = rec["io"]
    if sorted(io) != ["alarm", "detect", "ingest", "map"]:
        return {k: float("inf") for k in NUMBERS}
    if dtype == jnp.float32:
        keep, score = io["ingest"]["keep"], io["alarm"]["score"]
    else:
        got = chain(dtype)
        keep, score = got["keep"], got["score"]
    return {"keep_flips": float(jnp.sum(jnp.asarray(keep) != want["keep"])),
            "score_err": abs(float(score) - float(want["score"]))}


def check(kept: List[dict], config: Dict, ref,
          dtype=jnp.float32) -> common.Checks:
    worst = {k: (0.0 if kept else float("inf")) for k in NUMBERS}
    memo: dict = {}
    for rec in kept:
        got = readings(rec, ref, dtype, memo)
        for k in NUMBERS:
            worst[k] = max(worst[k], got[k])
    checks = common.Checks()
    for k in NUMBERS:
        checks.add(k, worst[k], config["limits"][k])
    return checks
