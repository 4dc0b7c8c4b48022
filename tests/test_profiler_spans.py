"""The engines' own host spans on the profiler's clock.

``ServingEngine(annotate=True)`` and ``WorkflowEngine(annotate=True)``
open ``jax.profiler.TraceAnnotation`` spans at their layer boundaries.
Run under the JAX profiler on the CPU, the host plane holds each span
name, nested as the engine's calls are; with ``annotate=False`` no
annotation is ever built.  Also here: the decode program's stable name,
and the workflow input passed in by the caller.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.continuum.network import ContinuumNetwork
from repro.continuum.orbits import Constellation
from repro.serverless.engine import WorkflowEngine
from repro.serverless.workflow import flood_workflow, make_payload

PREFIXES = ("serve.", "workflow.")
FLOOD_BODIES = ("ingest", "detect", "map", "alarm")


@pytest.fixture(scope="module")
def net():
    return ContinuumNetwork(Constellation(n_planes=8, sats_per_plane=8))


def _serve(annotate: bool, n_requests: int = 2):
    from repro.configs.base import get_smoke_config
    from repro.models import init_params
    from repro.serving.engine import Request, ServingEngine
    cfg = get_smoke_config("h2o-danube-1.8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, n_slots=1, max_len=32, eos_id=-1,
                        annotate=annotate)
    for i in range(n_requests):
        eng.submit(Request(i, np.arange(2, 5 + i, dtype=np.int32),
                           max_new=3))
    assert len(eng.run_until_done(max_steps=100)) == n_requests
    return {"requests": n_requests}


def _flood(annotate: bool):
    net = ContinuumNetwork(Constellation(n_planes=8, sats_per_plane=8))
    eng = WorkflowEngine(net, strategy="databelt", real_compute=True,
                         annotate=annotate)
    m = eng.run_instance(flood_workflow("span"), 2e5)
    assert m.compute_time > 0
    return {"requests": 1}


RUNS = {"serving": _serve, "workflow": _flood}

# span -> the program span that must enclose it (None: outermost)
PARENTS = {
    "serving": {"serve.step": {None}, "serve.admit": {"serve.step"},
                "serve.prefill": {"serve.admit"},
                "serve.emit": {"serve.prefill", "serve.step"}},
    "workflow": dict(
        {"workflow.instance": {None}},
        **{f"workflow.{p}": {"workflow.instance"}
           for p in ("place", "ingress", "fetch", "execute", "offload")},
        **{f"workflow.body.{b}": {"workflow.execute"}
           for b in FLOOD_BODIES}),
}


def _program_spans(logdir: Path):
    """[(name, start_ns, end_ns, parent name)] of the program spans on
    the one host line that holds them."""
    found = sorted(logdir.rglob("*.xplane.pb"))
    assert found, "the profiler wrote no trace"
    pd = jax.profiler.ProfileData.from_file(str(found[-1]))
    lines = []
    for plane in pd.planes:
        for line in plane.lines:
            evs = sorted(((ev.name, ev.start_ns,
                           ev.start_ns + ev.duration_ns)
                          for ev in line.events
                          if ev.name.startswith(PREFIXES)),
                         key=lambda x: (x[1], -x[2]))
            if evs:
                lines.append(evs)
    assert len(lines) == 1, "program spans on more than one line"
    out, open_ = [], []
    for name, s, e in lines[0]:
        while open_ and open_[-1][2] <= s:
            open_.pop()
        if open_:
            assert e <= open_[-1][2], f"{name} overlaps {open_[-1][0]}"
        out.append((name, s, e, open_[-1][0] if open_ else None))
        open_.append((name, s, e))
    return out


@pytest.mark.parametrize("engine", sorted(RUNS))
def test_engine_spans_nest_on_the_host_plane(engine, tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        ran = RUNS[engine](annotate=True)
    finally:
        jax.profiler.stop_trace()
    spans = _program_spans(tmp_path)
    want = PARENTS[engine]
    assert {n for n, *_ in spans} == set(want)
    for name, _, _, parent in spans:
        assert parent in want[name], f"{name} inside {parent}"
    count = {n: sum(x[0] == n for x in spans) for n in want}
    if engine == "serving":
        # one prefill span per prompt, never one per token
        assert count["serve.prefill"] == ran["requests"]
        assert count["serve.admit"] == count["serve.step"]
    else:
        assert all(count[f"workflow.body.{b}"] == 1 for b in FLOOD_BODIES)


@pytest.mark.parametrize("engine", sorted(RUNS))
def test_annotate_off_builds_no_annotation(engine, monkeypatch):
    def refuse(*args, **kwargs):
        raise AssertionError("TraceAnnotation built with annotate off")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    RUNS[engine](annotate=False)


def test_dag_path_emits_no_phase_spans(net, tmp_path):
    """Branches of a DAG run as concurrent kernel processes, so spans that
    cover a yield would not nest: only placement, the bodies and the
    instance's event loop are annotated there."""
    from repro.serverless.dag import diamond_workflow
    wf = diamond_workflow("dia", 2)
    for fn in wf.functions:
        fn.compute = lambda payload: {"x": jnp.ones(2)}
    eng = WorkflowEngine(net, strategy="databelt", real_compute=True,
                         annotate=True)
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.run_instance(wf, 2e5)
    finally:
        jax.profiler.stop_trace()
    names = {n for n, *_ in _program_spans(tmp_path)}
    assert names == {"workflow.instance", "workflow.place"} | {
        f"workflow.body.{fn.name}" for fn in wf.functions}


def test_decode_program_is_named():
    """The device trace names a program by its jitted function: the
    engine's decode is ``jit_decode_step`` and its prompt chunk
    ``jit_prefill_chunk``, one module per program."""
    from repro.configs.base import get_smoke_config
    from repro.models import init_params
    from repro.serving.engine import ServingEngine
    cfg = get_smoke_config("h2o-danube-1.8b")
    eng = ServingEngine(cfg, None, n_slots=1, max_len=16)
    params = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    text = eng._decode_step.lower(params, eng.cache,
                                  jax.ShapeDtypeStruct((1, 1), jnp.int32),
                                  np.int32(0)).as_text()
    assert "module @jit_decode_step" in text
    i32 = np.int32(0)
    text = eng._prefill_chunk.lower(
        params, eng.cache, jax.ShapeDtypeStruct((1, eng.chunk), jnp.int32),
        i32, i32, i32).as_text()
    assert "module @jit_prefill_chunk" in text


def test_workflow_input_is_passed_in(net, monkeypatch):
    """``run_instance(payload=...)`` hands the caller's input to the first
    body; the engine makes none of its own."""
    import repro.serverless.engine as engine_mod
    given = make_payload(2e5, with_sar=False)
    given["frames"] = given["frames"] * 3.0
    seen = []
    wf = flood_workflow("given")
    ingest = wf.fn("ingest")
    body = ingest.compute

    def spy(payload):
        seen.append(payload)
        return body(payload)
    ingest.compute = spy

    def refuse(*args, **kwargs):
        raise AssertionError("the engine made its own input")
    monkeypatch.setattr(engine_mod, "make_payload", refuse)
    eng = WorkflowEngine(net, strategy="databelt", real_compute=True)
    m = eng.run_instance(wf, 2e5, payload=given)
    assert m.compute_time > 0
    assert len(seen) == 1 and seen[0]["frames"] is given["frames"]
