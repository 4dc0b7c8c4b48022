"""HLO collective parser + serving engine + planner mesh bridge."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.mesh import make_mesh
from repro.roofline.hlo import collective_stats, total_collective_bytes

HLO_SNIPPET = """
HloModule test
fused {
  %p0 = f32[16,128]{1,0} parameter(0)
}
ENTRY main {
  %x = f32[16,128]{1,0} parameter(0)
  %y = bf16[4,8]{1,0} parameter(1)
  %ar = f32[16,128]{1,0} all-reduce(%x), replica_groups={}
  %ag = f32[64,128]{1,0} all-gather(%x), dimensions={0}
  %rs = f32[4,128]{1,0} reduce-scatter(%x), dimensions={0}
  %cp = bf16[4,8]{1,0} collective-permute(%y), source_target_pairs={{0,1}}
  ROOT %t = (f32[16,128]{1,0}) tuple(%ar)
}
"""


def test_collective_parser():
    st = collective_stats(HLO_SNIPPET)
    f16_128 = 16 * 128 * 4
    assert st["all-reduce"]["count"] == 1
    assert st["all-reduce"]["operand_bytes"] == f16_128
    assert st["all-gather"]["operand_bytes"] == f16_128
    assert st["all-gather"]["output_bytes"] == 64 * 128 * 4
    assert st["reduce-scatter"]["operand_bytes"] == f16_128
    assert st["collective-permute"]["operand_bytes"] == 4 * 8 * 2
    assert total_collective_bytes(st) == 3 * f16_128 + 4 * 8 * 2


def test_parser_on_real_compiled_module():
    mesh = make_mesh((1,), ("d",))

    def f(x):
        return x @ x.T

    c = jax.jit(f).lower(jax.ShapeDtypeStruct((8, 8), jnp.float32)).compile()
    st = collective_stats(c.as_text())
    assert total_collective_bytes(st) == 0   # single device: no collectives


def test_serving_engine_completes():
    from repro.configs.base import get_smoke_config
    from repro.models import init_params
    from repro.serving.engine import Request, ServingEngine
    cfg = get_smoke_config("h2o-danube-1.8b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, n_slots=2, max_len=64)
    for i in range(3):
        eng.submit(Request(i, np.array([5, 7, 11], np.int32), max_new=4))
    done = eng.run_until_done(max_steps=200)
    assert len(done) == 3
    assert all(1 <= len(r.tokens_out) <= 4 for r in done)


def test_serving_engine_compiles_decode_once():
    """Prompts of three lengths share one compiled chunk program, and the
    decode steps one decode program: positions, last rows and lanes go in
    with the same dtype and weak type on every call."""
    from repro.configs.base import get_smoke_config
    from repro.models import init_params
    from repro.serving.engine import Request, ServingEngine
    cfg = get_smoke_config("gemma3-1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    eng = ServingEngine(cfg, params, n_slots=2, max_len=32)
    for i, n in enumerate((3, 5, 4)):
        eng.submit(Request(i, np.arange(2, 2 + n, dtype=np.int32),
                           max_new=3))
    assert len(eng.run_until_done(max_steps=100)) == 3
    assert eng._decode_step._cache_size() == 1
    assert eng._prefill_chunk._cache_size() == 1


def test_serving_engine_serves_greedy_continuation_of_prompt():
    """A lone request's tokens are the greedy continuation of its prompt:
    the first comes from the prompt's logits, each later one from the
    sequence served so far (checked against prefill over that sequence)."""
    from repro.configs.base import get_smoke_config
    from repro.models import forward_prefill, init_params
    from repro.serving.engine import Request, ServingEngine
    cfg = get_smoke_config("gemma3-1b")
    params = init_params(jax.random.PRNGKey(0), cfg)
    prompt = np.array([5, 9, 2, 7], np.int32)
    eng = ServingEngine(cfg, params, n_slots=2, max_len=32, eos_id=-1)
    eng.submit(Request(0, prompt, max_new=5))
    served = eng.run_until_done()[0].tokens_out
    seq, want = list(prompt), []
    for _ in range(5):
        logits, _ = forward_prefill(params, cfg,
                                    {"tokens": jnp.asarray([seq])})
        want.append(int(jnp.argmax(logits[0, -1].astype(jnp.float32))))
        seq.append(want[-1])
    assert served == want


def test_mesh_planner_bridge():
    from repro.core.planner import (LayoutCandidate, mesh_topology,
                                    plan_mesh_layout, score_layout)
    mesh = make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}
    g = mesh_topology(FakeMesh())
    assert len(g.nodes) == 512
    # Eq. 9 picks the layout that avoids the slow pod axis
    a = LayoutCandidate("cross_pod", {}, {"pod": 1e9})
    b = LayoutCandidate("in_pod", {}, {"model": 1e9})
    assert plan_mesh_layout([a, b], FakeMesh()).name == "in_pod"
    assert score_layout(a, FakeMesh()) > score_layout(b, FakeMesh())
