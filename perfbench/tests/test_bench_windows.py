"""One serving and one workflow window at tiny sizes on the CPU, with the
harness's look for a chip skipped: what a run reports, its check, the
control and the faults the check must catch."""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import common, tracing

DATA = common.BENCH_DIR / "tests" / "data"
SERVE = common.load_module("runners", "serve")
FLOW = common.load_module("runners", "workflow")
TRANSFORMER = common.load_module("refs", "transformer")
FLOOD = common.load_module("refs", "flood")
PEAK = {"flops_per_s": 197e12, "bytes_per_s": 819e9}


def _json(name):
    return json.loads((DATA / name).read_text())


def _serve(seconds=0.5, trace=False, seed=2**33 + 1):
    return SERVE.run(_json("tiny-transformer.json"), _json("tiny-serving.json"),
                     seed, seconds, trace, jax.devices(), time.perf_counter(),
                     TRANSFORMER, PEAK)


def _flood(seconds=0.3, trace=False, seed=2**33 + 2):
    return FLOW.run(_json("tiny-flood.json"), _json("tiny-workflow.json"),
                    seed, seconds, trace, jax.devices(), time.perf_counter(),
                    FLOOD, PEAK)


def _fake_capture(segment):
    segment()
    return {"window_s": 1.0, "busy_s": 0.25, "chips": 1, "idle_pct": 75.0,
            "device_ops": [["fusion", 0.25]], "idle_gaps": [["x", 0.75]]}


def test_serving_window_reports_and_checks(monkeypatch):
    monkeypatch.setattr(tracing, "capture", _fake_capture)
    out = _serve(trace=True)
    assert out["checks"].correct, out["checks"].items
    assert out["attempted"] >= 1 and out["failed"] == 0
    e2e = out["e2e"]
    assert set(e2e) == {"ttft_p95_ms", "itl_p95_ms", "tokens_per_s",
                        "setup_s"}
    assert all(v["value"] > 0 for v in e2e.values())
    run = out["run"]
    assert run.trace["idle_pct"] == 75.0
    for name in ("prefill_mfu", "decode_mfu"):
        v = common.load_module("metrics", name).read(run)
        assert 0 < v < 100
    assert common.load_module("metrics", "body_ms.flood").read(run) is None
    assert out["checks"].items["compiles_in_window"]["value"] == 0


def test_emit_wrapper_times_against_a_fake_clock(monkeypatch):
    """TTFT is the prompt's decode calls, each gap one decode call."""
    from repro.serving.engine import Request, ServingEngine
    cfg = SERVE.model_config(_json("tiny-transformer.json"))
    params = SERVE.make_weights(cfg, 3)
    eng = ServingEngine(cfg, params, n_slots=1, max_len=64, eos_id=-1)
    t = {"now": 0.0}
    monkeypatch.setattr(common, "clock", lambda: t["now"])
    decode = eng._decode

    def slow_decode(*a):
        t["now"] += 1.0
        return decode(*a)
    eng._decode = slow_decode
    rec = SERVE.Recorder(eng, annotate=False)
    req = rec.serve(Request(req_id=5, prompt=np.arange(2, 9, dtype=np.int32),
                            max_new=4))
    times = rec.emits[5]
    assert req.submit == 0.0
    assert times[0] - req.submit == 7.0          # 7 prompt tokens
    assert [b - a for a, b in zip(times, times[1:])] == [1.0, 1.0, 1.0]
    assert len(rec.logits[5]) == 4


def test_workflow_window_reports_and_checks(monkeypatch):
    monkeypatch.setattr(tracing, "capture", _fake_capture)
    out = _flood(trace=True)
    assert out["checks"].correct, out["checks"].items
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert out["e2e"]["instance_p95_ms"]["value"] > 0
    run = out["run"]
    body = common.load_module("metrics", "body_ms.flood").read(run)
    rt = common.load_module("metrics", "runtime_ms.flood").read(run)
    assert body > 0 and rt > 0
    assert common.load_module("metrics", "device_idle_pct.flood").read(
        run) == 75.0
    assert common.load_module("metrics", "prefill_mfu").read(run) is None


# ---------------------------------------------------------------------------
# the control and the faults
# ---------------------------------------------------------------------------
def test_serving_control_fails_the_limits():
    out = _serve()
    params, picked = out["kept"]
    cfg = _json("tiny-transformer.json")
    checks = SERVE.check(params, cfg, picked, {}, TRANSFORMER, quant="fp8")
    assert not checks.correct, checks.items


def test_workflow_control_fails_the_limits():
    out = _flood()
    checks = FLOW.check(out["kept"], _json("tiny-flood.json"), FLOOD,
                        dtype=jnp.bfloat16)
    assert not checks.correct, checks.items


def test_served_token_altered_where_produced_fails(monkeypatch):
    from repro.serving.engine import ServingEngine
    emit = ServingEngine._emit

    def altered(self, i, t):
        r = self.slots[i]
        if len(r.tokens_out) == 2:
            t = (t + 1) % self.cfg.vocab_size
        emit(self, i, t)
    monkeypatch.setattr(ServingEngine, "_emit", altered)
    out = _serve()
    assert not out["checks"].correct
    assert out["checks"].items["gap"]["value"] > 0.1


def test_decode_leaving_its_cache_unchanged_fails(monkeypatch):
    import repro.serving.engine as engine_mod
    decode = engine_mod.forward_decode

    def stale(params, cfg, cache, tok, pos):
        logits, _ = decode(params, cfg, cache, tok, pos)
        return logits, cache
    monkeypatch.setattr(engine_mod, "forward_decode", stale)
    out = _serve()
    assert not out["checks"].correct
    assert out["checks"].items["logit_err"]["value"] > 0.1


def test_body_answer_altered_where_produced_fails(monkeypatch):
    import repro.serverless.workflow as wf
    detect = wf.detect_fn

    def altered(payload):
        out = detect(payload)
        return {"detections": out["detections"] + 1e-3}
    monkeypatch.setattr(wf, "detect_fn", altered)
    out = _flood()
    assert not out["checks"].correct
    assert out["checks"].items["score_err"]["value"] > 5e-4


def test_state_passed_between_bodies_altered_fails(monkeypatch):
    import repro.serverless.workflow as wf
    mapper = wf.map_fn

    def stale(payload):
        out = mapper(payload)
        return {**out, "detections": jnp.zeros_like(out["detections"])}
    monkeypatch.setattr(wf, "map_fn", stale)
    out = _flood()
    assert not out["checks"].correct


def test_half_of_the_frames_left_out_fails(monkeypatch):
    import repro.serverless.workflow as wf
    detect = wf.detect_fn

    def half(payload):
        frames = payload["frames"]
        return detect({**payload, "frames": frames[: len(frames) // 2]})
    monkeypatch.setattr(wf, "detect_fn", half)
    out = _flood()
    assert not out["checks"].correct
    assert out["checks"].items["score_err"]["value"] > 1e-4


def test_command_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(common.BENCH_DIR / "run.py"), "--workload",
         "danube-conv", "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120,
        cwd=str(common.CHECKOUT))
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr
