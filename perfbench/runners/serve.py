"""Serving cells: one closed-loop client through ``ServingEngine``.

Set-up makes the weights on the chip from the seed in one jitted call,
builds ``ServingEngine(n_slots=1)`` with an end-of-sequence id no token
can take (output lengths are the mix's own), and serves one short request,
which compiles the decode program and the host-side argmax.

The window: the client submits a request, steps the engine until it is
done, and submits the next, until ``seconds`` have passed; the request
in flight then runs to its end, and the window closes at its last token.
Token times come from wrapping ``ServingEngine._emit``, which runs after
the host has synced on the argmax.  The logits that chose each token are
kept by wrapping the decode call.

With ``trace`` on, host annotations mark admission, prompt feeding, each
step and each emit, and after the window a further ``trace_seconds`` of
the same traffic runs under the profiler.

Then the check: a sample of finished requests, drawn from the seed with
the longest among them, goes through the plain reference over prompt and
served tokens, and two numbers are compared with the configuration's
limits (``gap``, ``logit_err``; see ``check``).
"""
from __future__ import annotations

import dataclasses
from contextlib import nullcontext
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import common, traffic, tracing

NO_TOKEN = -1          # end-of-sequence id that no token can take
WARMUP = (8, 3)        # prompt tokens, max_new of the set-up request


def model_config(c: Dict):
    from repro.configs.base import ModelConfig
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    kw = {k: v for k, v in c.items() if k in fields}
    kw["layer_pattern"] = tuple(kw["layer_pattern"])
    return ModelConfig(family="dense", **kw)


# ---------------------------------------------------------------------------
# weights: the program's layout, the benchmark's values
# ---------------------------------------------------------------------------
def _scale(name: str, shape) -> float:
    if name == "embed":
        return 1.0
    if len(shape) == 1 or name in ("ln1", "ln2", "final_norm"):
        return 0.1                       # norm gains 1 + N(0, 0.1^2)
    return float(shape[-2 if name != "lm_head" else -1]) ** -0.5


def _chunks(n: int) -> int:
    return max(d for d in range(1, 17) if n % d == 0)


def make_weights(cfg, seed: int):
    """Every leaf of the program's parameter tree, drawn from the seed on
    the device in one jitted call, in the dtype it is served in.  Leaves
    are drawn in slices along their first axis to bound temporaries."""
    from repro.models import init_params
    shapes = jax.eval_shape(lambda k: init_params(k, cfg),
                            jax.random.PRNGKey(0))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def leaf(key, name, s):
        sc = _scale(name, s.shape)
        if len(s.shape) == 1:
            return (jax.random.normal(key, s.shape, jnp.float32)
                    * sc).astype(s.dtype)
        n = _chunks(s.shape[0])
        part = (s.shape[0] // n,) + tuple(s.shape[1:])
        out = jax.lax.map(
            lambda k: (jax.random.normal(k, part, s.dtype)
                       * jnp.asarray(sc, s.dtype)),
            jax.random.split(key, n))
        return out.reshape(s.shape)

    @jax.jit
    def fill(key):
        leaves = [leaf(jax.random.fold_in(key, i), path[-1].key, s)
                  for i, (path, s) in enumerate(flat)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return fill(common.prng_key(seed))


# ---------------------------------------------------------------------------
# the instrumented engine
# ---------------------------------------------------------------------------
class Recorder:
    """Wraps one engine: emit times and choosing logits per request, and
    host annotations when ``annotate`` is on."""

    def __init__(self, eng, annotate: bool):
        self.eng = eng
        self.emits: Dict[int, List[float]] = {}
        self.logits: Dict[int, list] = {}
        self._last = None
        self.annotate = annotate
        decode, emit = eng._decode, eng._emit

        def decode_kept(*args):
            logits, cache = decode(*args)
            self._last = logits
            return logits, cache

        def emit_timed(i, t):
            now = common.clock()
            with self._span("serve.emit"):
                r = eng.slots[i]
                self.emits.setdefault(r.req_id, []).append(now)
                self.logits.setdefault(r.req_id, []).append(self._last)
                emit(i, t)

        eng._decode, eng._emit = decode_kept, emit_timed
        if annotate:
            admit, feed = eng._admit, eng._feed_prompt

            def admit_ann():
                with self._span("serve.admit"):
                    admit()

            def feed_ann(i, req):
                with self._span("serve.prefill"):
                    feed(i, req)

            eng._admit, eng._feed_prompt = admit_ann, feed_ann

    def _span(self, name: str):
        return jax.profiler.TraceAnnotation(name) if self.annotate \
            else nullcontext()

    def serve(self, req, stop: float = float("inf")):
        """Submit ``req`` and step the engine until it is done, or until
        the clock passes ``stop``."""
        req.submit = common.clock()
        self.eng.submit(req)
        self.finish(req, stop)
        return req

    def finish(self, req, stop: float = float("inf")):
        while not req.done and common.clock() < stop:
            with self._span("serve.step"):
                self.eng.step()


def _requests(mix, vocab, seed, first_id: int):
    from repro.serving.engine import Request
    for i, (prompt, max_new) in enumerate(
            traffic.serving_requests(mix, vocab, seed), first_id):
        yield Request(req_id=i, prompt=prompt, max_new=max_new)


@dataclasses.dataclass
class ServeRun:
    """What a window leaves for the metric readers and the check."""
    kind: str
    config: Dict
    peak: Optional[Dict]
    requests: List[dict]
    trace: Optional[Dict] = None


def _record(r, rec) -> dict:
    return {"req_id": r.req_id, "prompt_len": len(r.prompt),
            "tokens": list(r.tokens_out), "submit": r.submit,
            "emits": rec.emits.get(r.req_id, []), "done": r.done}


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
def run(config: Dict, mix: Dict, seed: int, seconds: float, trace: bool,
        devs, t_process: float, ref, peak: Optional[Dict] = None) -> dict:
    from repro.serving.engine import Request, ServingEngine
    cfg = model_config(config)
    params = jax.block_until_ready(make_weights(cfg, seed))
    common.note(f"set-up: weights made at {common.clock() - t_process:.3f} s")
    eng = ServingEngine(cfg, params, n_slots=1, max_len=config["max_len"],
                        eos_id=NO_TOKEN)
    rec = Recorder(eng, annotate=trace)
    P, K = WARMUP
    rec.serve(Request(req_id=-1, prompt=np.arange(2, 2 + P, dtype=np.int32),
                      max_new=K))
    jax.block_until_ready(eng.cache)
    compiles = common.CompileCounter()

    # the window
    reqs = _requests(mix, cfg.vocab_size, seed, first_id=0)
    started = []
    compiles.on = True
    t0 = common.clock()
    setup_s = t0 - t_process
    common.note(f"set-up: warm at {setup_s:.3f} s")
    deadline = t0 + seconds
    while common.clock() < deadline:
        started.append(rec.serve(next(reqs)))
    t1 = common.clock()
    compiles.on = False
    in_compiles = compiles.count

    trace_red = None
    if trace:
        # the same traffic, cut at ``trace_seconds`` mid-request; the
        # request in flight then ends outside the trace
        last = []

        def segment():
            stop = common.clock() + mix["trace_seconds"]
            while common.clock() < stop:
                last[:] = [rec.serve(next(reqs), stop)]
        trace_red = tracing.capture(segment)
        for r in last:
            rec.finish(r)

    device = common.device_info(devs)
    finished = [r for r in started if r.done]
    records = [_record(r, rec) for r in started]
    picked = sample(finished, seed, mix["check_tokens"])
    served = {r.req_id: rec.logits.pop(r.req_id, []) for r in picked}
    # the program's state goes before the reference runs; the weights
    # and the sample's choosing logits stay
    rec.logits.clear()
    del eng.cache
    eng = None
    checks = check(params, config, picked, served, ref)
    checks.add("tokens_out_of_range", sum(
        not 0 <= t < cfg.vocab_size for r in finished for t in r.tokens_out),
        0)
    checks.add("compiles_in_window", in_compiles, 0)
    checks.add("unfinished_requests", len(started) - len(finished), 0)

    ttft = [rq["emits"][0] - rq["submit"] for rq in records if rq["emits"]]
    itl = [b - a for rq in records for a, b in zip(rq["emits"],
                                                  rq["emits"][1:])]
    n_tokens = sum(len(rq["emits"]) for rq in records)
    window_s = t1 - t0
    e2e = {"ttft_p95_ms": common.metric(1e3 * common.percentile(ttft, 95),
                                        "ms"),
           "itl_p95_ms": common.metric(1e3 * common.percentile(itl, 95),
                                       "ms"),
           "tokens_per_s": common.metric(n_tokens / window_s, "tokens/s"),
           "setup_s": common.metric(setup_s, "s")}
    return {"e2e": e2e, "checks": checks, "device": device,
            "attempted": len(started),
            "failed": len(started) - len(finished),
            "run": ServeRun("serve", config, peak, records, trace_red),
            "kept": (params, picked)}


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------
def sample(finished, seed: int, want_tokens: int):
    """The longest finished request, then others in an order drawn from
    the seed, until ``want_tokens`` served tokens are in the sample."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: (len(r.prompt) + len(r.tokens_out),
                                           -r.req_id))
    out = [longest]
    got = len(longest.tokens_out)
    for i in traffic.seeded_order(seed, len(finished)):
        r = finished[int(i)]
        if got >= want_tokens:
            break
        if r is not longest:
            out.append(r)
            got += len(r.tokens_out)
    return out


def readings(params, config, req, served_logits, ref, quant=None) -> dict:
    """For one request: the reference's logits at each choosing position
    over prompt + served tokens, against what was served.

    - ``gap``: how far the served token's reference logit lies below the
      reference's best, the widest over the request's tokens;
    - ``logit_err``: the largest |served logit - reference logit| over the
      vocabulary and the request's tokens.
    With ``quant`` set, the control: the reference computed with rounded
    weights stands in for what was served, and its own top token is the
    one whose gap is read."""
    P, toks = len(req.prompt), np.asarray(req.tokens_out, np.int32)
    seq = np.concatenate([np.asarray(req.prompt, np.int32), toks[:-1]])
    want = ref.logits(params, config, seq)[P - 1:]              # (K, V)
    if quant is None:
        if len(served_logits) != len(toks):
            return {"gap": float("inf"), "logit_err": float("inf")}
        got = jnp.concatenate([jnp.reshape(x, (1, -1)) for x in
                               served_logits]).astype(jnp.float32)
        chosen = jnp.asarray(toks)
    else:
        got = ref.logits(params, config, seq, quant=quant)[P - 1:]
        chosen = jnp.argmax(got, axis=-1)
    picked = jnp.take_along_axis(want, chosen[:, None], axis=1)[:, 0]
    gap = jnp.max(jnp.max(want, axis=1) - picked)
    err = jnp.max(jnp.abs(got - want))
    return {"gap": float(gap), "logit_err": float(err)}


def check(params, config, picked, served, ref,
          quant=None) -> common.Checks:
    """The widest ``gap`` and ``logit_err`` over the sampled requests."""
    worst = {"gap": float("inf"), "logit_err": float("inf")}
    if picked:
        worst = {"gap": 0.0, "logit_err": 0.0}
    for req in picked:
        got = readings(params, config, req, served.get(req.req_id, []), ref,
                       quant)
        for k in worst:
            worst[k] = max(worst[k], got[k])
    checks = common.Checks()
    for k, v in worst.items():
        checks.add(k, v, config["limits"][k])
    return checks
