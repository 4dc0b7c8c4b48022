"""The routed-experts kernel (``repro.kernels.routed_experts``) in the
Pallas interpreter at tiny widths: ``moe_held``'s routed form (the
kernel) equals its dense form on the same tokens, the float32 oracle
``ref.py`` equals both, and the shape rule picks the form."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config, get_smoke_config
from repro.kernels.routed_experts import routed_experts, routed_experts_ref
from repro.models import moe as M
from repro.models.modules import activation

MELLUM = "mellum2-12b-a2.5b"


def _cfg(arch=MELLUM, k=2, n_held=0, first_held=0):
    """The smoke configuration (8 experts of 64, d_model 64), float32."""
    cfg = get_smoke_config(arch)
    moe = dataclasses.replace(cfg.moe, experts_per_token=k, n_held=n_held,
                              first_held=first_held)
    return dataclasses.replace(cfg, moe=moe, param_dtype="float32")


def _x(cfg, T, seed=1):
    return jax.random.normal(jax.random.PRNGKey(seed), (1, T, cfg.d_model),
                             jnp.float32)


def _dense(p, x, cfg):
    """``moe_held``'s dense form on x's tokens: x followed by enough rows
    that are not ``valid`` for the shape rule to take that form."""
    T, D = x.shape[1], x.shape[2]
    pad = cfg.moe.held
    assert not M.reads_routed(T + pad, cfg)
    xx = jnp.concatenate([x, jnp.zeros((1, pad, D), x.dtype)], 1)
    valid = (jnp.arange(T + pad) < T)[None]
    return M.moe_held(p, xx, cfg, valid)[0][:, :T]


def _oracle(p, x, cfg):
    """``ref.py`` on the layer's routing, plus the dense shared MLP."""
    m = cfg.moe
    xf = x.reshape(-1, cfg.d_model)
    gate, idx, _ = M._route(xf, p["router"], m.experts_per_token)
    y = routed_experts_ref(xf, gate, M._held_index(idx, cfg, None),
                           p["w_gate"], p["w_up"], p["w_down"],
                           act=activation(cfg.act)).reshape(x.shape)
    if "dense" in p:
        y = y + M.mlp(p["dense"], x, cfg.act)
    return y


def _split_router(p, cfg, first, n):
    """A router that sends a token whose feature 0 is positive to experts
    ``first``..``first + n - 1`` and one whose feature 0 is negative to
    the others (a little noise breaks ties)."""
    noise = 0.01 * jax.random.normal(jax.random.PRNGKey(9),
                                     p["router"].shape)
    side = jnp.where((jnp.arange(cfg.moe.n_experts) >= first)
                     & (jnp.arange(cfg.moe.n_experts) < first + n), 1., -1.)
    return {**p, "router": noise.at[0].add(10.0 * side)}


# (arch, k, n_held, first_held, tokens, what)
CASES = {
    "B1": (MELLUM, 2, 4, 4, 1, None),
    "B3_shared_experts": (MELLUM, 2, 0, 0, 3, "repeat"),
    "first_half_held": (MELLUM, 2, 4, 0, 1, None),
    "second_half_held": (MELLUM, 2, 4, 4, 1, None),
    "token_without_held_choice": (MELLUM, 1, 4, 4, 3, "split"),
    "all_experts_held": (MELLUM, 2, 0, 0, 1, None),
    "dense_shared_mlp": ("arctic-480b", 2, 0, 0, 2, None),
}


@pytest.mark.parametrize("case", list(CASES))
def test_routed_form_equals_dense_form_and_oracle(case):
    """float32 weights: kernel, dense form and oracle differ by summation
    order only (1e-5 of the largest output)."""
    arch, k, n_held, first_held, T, what = CASES[case]
    cfg = _cfg(arch, k, n_held, first_held)
    assert M.reads_routed(T, cfg)
    p = M.init_moe(jax.random.PRNGKey(0), cfg)
    x = _x(cfg, T)
    if what == "repeat":           # tokens 0 and 2 route alike
        x = x.at[0, 2].set(x[0, 0])
    if what == "split":            # token 1 routes to experts 0-3 only
        p = _split_router(p, cfg, 0, 4)
        x = x.at[0, :, 0].set(jnp.asarray([-3.0, 3.0, -3.0]))
    xf = x.reshape(-1, cfg.d_model)
    _, idx, _ = M._route(xf, p["router"], k)
    held = np.asarray(M._held_index(idx, cfg, None)) >= 0
    if what == "repeat":
        assert np.array_equal(np.asarray(idx[0]), np.asarray(idx[2]))
    if what == "split":
        assert held.tolist() == [[True], [False], [True]]
    y, aux = M.moe_held(p, x, cfg)
    dense = _dense(p, x, cfg)
    want = _oracle(p, x, cfg)
    tol = 1e-5 * float(jnp.abs(want).max())
    np.testing.assert_allclose(np.asarray(y), np.asarray(dense), atol=tol)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=tol)
    assert np.isfinite(float(aux))
    if what == "split":
        rows = np.asarray(y[0] - (M.mlp(p["dense"], x, cfg.act)[0]
                                  if "dense" in p else 0))
        assert np.all(rows[1] == 0) and np.all(rows[[0, 2]] != 0)


@pytest.mark.parametrize("layer", [0, 2])
def test_kernel_reads_its_layer_from_the_stack(layer):
    """Weights stacked over 3 layers, the layer's index a traced scalar:
    the result is that layer's, against the oracle."""
    T, k, E, D, F = 2, 3, 5, 128, 256
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    x = jax.random.normal(ks[0], (T, D))
    w = [jax.random.normal(ks[i], (3, E) + s) * s[0] ** -0.5
         for i, s in ((1, (D, F)), (2, (D, F)), (3, (F, D)))]
    expert = jnp.asarray([[4, -1, 0], [4, 7, 2]], jnp.int32)
    gate = jax.nn.softmax(jax.random.normal(ks[4], (T, k)), -1)
    act = activation("silu")
    got = jax.jit(lambda i: routed_experts(x, gate, expert, *w, act=act,
                                           layer=i))(jnp.int32(layer))
    want = routed_experts_ref(x, gate, expert, *(v[layer] for v in w),
                              act=act)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5 * float(jnp.abs(want).max()))


@pytest.mark.parametrize("rows,kernel", [(1, True), (3, True), (4, False),
                                         (512, False)])
def test_shape_rule_follows_the_row_count(rows, kernel):
    """The benchmark's Mellum share (top 8, 32 held): a decode token (and
    up to 3) takes the kernel; 4 rows, whose 32 pairs could reach every
    held expert, and a 512-row chunk take the dense form."""
    cfg = get_config(MELLUM)
    cfg = dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe,
                                                           n_held=32))
    p = jax.eval_shape(lambda key: M.init_moe(key, cfg),
                       jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, rows, cfg.d_model), jnp.bfloat16)
    jaxpr = str(jax.make_jaxpr(lambda p, x: M.moe_held(p, x, cfg))(p, x))
    assert M.reads_routed(rows, cfg) == kernel
    assert ("pallas_call" in jaxpr) == kernel
