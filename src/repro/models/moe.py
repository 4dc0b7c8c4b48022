"""Top-k mixture-of-experts: the held share on one device, and expert
parallelism over the ``model`` axis.

* ``moe_held`` — what one device runs (``moe_layer`` without a mesh): a
  dropless layer over the experts whose weights are here
  (``MoEConfig.n_held`` from ``first_held``).  It routes over all
  ``n_experts`` and computes every token's held choices, none dropped;
  what the experts held elsewhere would add is left out.  Where the
  rows' (token, choice) pairs cannot reach every held expert (a decode
  step) it reads only the routed experts' weights, through the
  ``routed_experts`` kernel; otherwise every row goes through every held
  expert.
* ``moe_dense`` — one-hot-free capacity oracle with GShard dropping;
  the numerical reference that ``moe_sharded``'s parity tests call.
* ``moe_sharded`` — sort-based dispatch inside ``shard_map``.  Experts are
  sharded over the ``model`` mesh axis ("EP-as-TP"): tokens stay sharded over
  the data axes and replicated over ``model``; every model-rank routes all its
  local tokens to its *local* experts and the outputs are psum-combined.  The
  collective cost therefore equals a dense Megatron FFN (one psum), with no
  extra all-to-all on the critical path — this is the "state fusion" story of
  the paper applied to expert state: per-expert fetches are fused into the one
  boundary collective that TP already pays for.  Capacity-dropped tokens
  fall back to the identity (residual) path, as in ``moe_dense``.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import context as dctx
from repro.kernels.routed_experts.ops import blocks_read, routed_experts
from repro.models.modules import activation, mlp, pdtype

EXPERT_WEIGHTS = ("w_gate", "w_up", "w_down")


def init_moe(key, cfg: ModelConfig) -> dict:
    """The router over all ``n_experts``; the weights of the held ones."""
    m = cfg.moe
    d, e, f = cfg.d_model, m.held, m.d_ff_expert
    dt = pdtype(cfg)
    ks = jax.random.split(key, 7)
    p = {
        "router": jax.random.normal(ks[0], (d, m.n_experts), jnp.float32)
        * d ** -0.5,
        "w_gate": jax.random.normal(ks[1], (e, d, f), dt) * d ** -0.5,
        "w_up": jax.random.normal(ks[2], (e, d, f), dt) * d ** -0.5,
        "w_down": jax.random.normal(ks[3], (e, f, d), dt) * f ** -0.5,
    }
    if m.dense_residual:
        fd = m.d_ff_dense or cfg.d_ff
        p["dense"] = {
            "w_gate": jax.random.normal(ks[4], (d, fd), dt) * d ** -0.5,
            "w_up": jax.random.normal(ks[5], (d, fd), dt) * d ** -0.5,
            "w_down": jax.random.normal(ks[6], (fd, d), dt) * fd ** -0.5,
        }
    return p


def _route(x_flat, router, k: int):
    """Returns (gate_weights (T,k) f32, expert_idx (T,k) i32, probs (T,E))."""
    logits = (x_flat.astype(jnp.float32) @ router)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, k)
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    return gate, idx, probs


def _aux_loss(probs, idx, n_experts: int):
    """Switch-style load-balance loss: E * sum_e f_e * P_e."""
    T, k = idx.shape
    one_hot = jax.nn.one_hot(idx, n_experts, dtype=jnp.float32).sum(1)
    f = one_hot.mean(0) / k
    p = probs.mean(0)
    return n_experts * jnp.sum(f * p)


def _capacity(T: int, k: int, E: int, cf: float) -> int:
    c = int(math.ceil(T * k / E * cf))
    c = max(c, min(T * k, 8))
    return min(c, T)


def _expert_ffn(bufs, p, act):
    h = jnp.einsum("ecd,edf->ecf", bufs, p["w_gate"])
    u = jnp.einsum("ecd,edf->ecf", bufs, p["w_up"])
    h = act(h) * u
    return jnp.einsum("ecf,efd->ecd", h, p["w_down"])


# ---------------------------------------------------------------------------
# oracle path
# ---------------------------------------------------------------------------
def moe_dense(params, x, cfg: ModelConfig, groups: int = 1):
    """One-hot-free dispatch oracle.  ``groups`` splits the tokens into
    contiguous capacity groups with per-group overflow dropping (GShard
    semantics): the sharded path competes tokens for expert capacity only
    within one data shard, so a parity comparison against it must pass
    ``groups = <data shards>`` — with the default 1 the whole batch is a
    single group (the standalone / smoke-test behaviour)."""
    m = cfg.moe
    B, S, D = x.shape
    T = B * S
    xf = x.reshape(T, D)
    gate, idx, probs = _route(xf, params["router"], m.experts_per_token)
    if groups < 1 or T % groups != 0:
        raise ValueError(
            f"groups={groups} must evenly divide the {T} tokens")
    G = groups
    Tg = T // G
    C = _capacity(Tg, m.experts_per_token, m.n_experts, m.capacity_factor)
    k_top = m.experts_per_token

    def dispatch_group(xg, gate_g, idx_g):
        flat_e = idx_g.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        starts = jnp.searchsorted(sorted_e, jnp.arange(m.n_experts))
        pos = jnp.arange(Tg * k_top) - starts[sorted_e]
        keep = pos < C
        e_idx = jnp.where(keep, sorted_e, m.n_experts)   # OOB -> dropped
        p_idx = jnp.where(keep, pos, C)
        tok = order // k_top

        buf = jnp.zeros((m.n_experts, C, D), x.dtype)
        buf = buf.at[e_idx, p_idx].set(xg[tok], mode="drop")
        out_buf = _expert_ffn(buf, params, activation(cfg.act))
        contrib = out_buf.at[e_idx, p_idx].get(mode="fill", fill_value=0.0)
        w = gate_g.reshape(-1)[order][:, None] * keep[:, None]
        return jnp.zeros((Tg, D), x.dtype).at[tok].add(
            (contrib * w).astype(x.dtype))

    y = jax.vmap(dispatch_group)(
        xf.reshape(G, Tg, D), gate.reshape(G, Tg, k_top),
        idx.reshape(G, Tg, k_top)).reshape(T, D)
    y = y.reshape(B, S, D)
    if "dense" in params:
        from repro.models.modules import mlp
        y = y + mlp(params["dense"], x, cfg.act)
    return y, _aux_loss(probs, idx, m.n_experts)


# ---------------------------------------------------------------------------
# sharded path (shard_map over the full mesh)
# ---------------------------------------------------------------------------
def _dispatch(xf, gate, idx, C: int, e0: int, e_loc: int, n_experts: int,
              k_top: int):
    """Sort-based dispatch of this rank's tokens to its local experts.
    Returns (buf (e_loc,C,D), combine_fn(out_buf) -> (T,D))."""
    T = xf.shape[0]
    flat_e = idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    starts = jnp.searchsorted(sorted_e, jnp.arange(n_experts))
    pos = jnp.arange(T * k_top) - starts[sorted_e]
    local = (sorted_e >= e0) & (sorted_e < e0 + e_loc)
    keep = (pos < C) & local
    e_idx = jnp.where(keep, sorted_e - e0, e_loc)
    p_idx = jnp.where(keep, pos, C)
    tok = order // k_top

    buf = jnp.zeros((e_loc, C, xf.shape[1]), xf.dtype)
    buf = buf.at[e_idx, p_idx].set(xf[tok], mode="drop")

    def combine(out_buf):
        contrib = out_buf.at[e_idx, p_idx].get(mode="fill", fill_value=0.0)
        w = gate.reshape(-1)[order][:, None] * keep[:, None]
        y = jnp.zeros((T, out_buf.shape[-1]), xf.dtype)
        return y.at[tok].add((contrib * w).astype(xf.dtype))

    return buf, combine


def moe_sharded(params, x, cfg: ModelConfig, decode: bool = False):
    """Expert parallelism over ``model`` with expert-ff FSDP over ``data``.

    * train/prefill: tokens stay data-sharded; each rank all-gathers its
      local experts' ff-shards over ``data`` (ZeRO-3 weight gather) and
      processes all its tokens for its experts; outputs psum over ``model``.
    * decode: tokens are tiny — all-gather *tokens* over data instead, keep
      weights fully sharded (2D expert TP: experts x ff-shard), psum_scatter
      the partial FFN outputs back.
    """
    rules = dctx.current()
    if rules is None:
        return moe_dense(params, x, cfg)
    m = cfg.moe
    mesh = rules.mesh
    ep_axis = rules.moe_axis
    ep = mesh.shape[ep_axis]
    fsdp_axis = rules.rules.get("moe_ff")
    if m.n_experts % ep != 0:
        return moe_dense(params, x, cfg)
    batch_spec = rules.spec(("batch", "seq", None))
    da = tuple(a for a in (rules.rules.get("batch") or ())
               if a in mesh.axis_names)
    # sequence-parallel output: emit the residual already sharded over the
    # model axis (psum_scatter instead of psum) — halves the wire bytes of
    # the boundary collective and its backward becomes a cheap all-gather.
    # This is the Databelt Offload idea at the tensor level: the state
    # leaves the "function" already placed where the consumer wants it.
    # Gated on head divisibility: with padded heads (arctic: 56 on a 16-way
    # axis) the attention block keeps activations in a padded layout and the
    # seq-sharded boundary forces GSPMD re-layouts that cost more than the
    # reduce-scatter saves (measured, EXPERIMENTS.md §Perf).
    sp_axis = rules.rules.get("act_seq") if not decode else None
    heads_even = cfg.n_heads % ep == 0
    sp = sp_axis == ep_axis and heads_even
    out_spec = rules.spec(("batch", "act_seq", None)) if sp else batch_spec
    in_spec = out_spec   # seq-sharded in AND out: the backward of the
    # input gather is a reduce-scatter, not an all-reduce

    act = activation(cfg.act)
    k_top = m.experts_per_token
    e_loc = m.n_experts // ep

    wg_spec = rules.spec(("experts", None, "moe_ff"))
    wd_spec = rules.spec(("experts", "moe_ff", None))

    def body(xl, router, wg, wu, wd):
        B, S, D = xl.shape
        r = jax.lax.axis_index(ep_axis)
        e0 = r * e_loc
        if decode and da:
            # 2D-TP: gather tokens over the data axes, partial-ff FFN
            xf = xl.reshape(B * S, D)
            xf = jax.lax.all_gather(xf, da, axis=0, tiled=True)
        else:
            if sp:
                xl = jax.lax.all_gather(xl, ep_axis, axis=1, tiled=True)
                S = xl.shape[1]
            xf = xl.reshape(B * S, D)
            if fsdp_axis:
                # ZeRO-3: reassemble this rank's expert ff-shards
                wg = jax.lax.all_gather(wg, fsdp_axis, axis=2, tiled=True)
                wu = jax.lax.all_gather(wu, fsdp_axis, axis=2, tiled=True)
                wd = jax.lax.all_gather(wd, fsdp_axis, axis=1, tiled=True)
        T = xf.shape[0]
        gate, idx, probs = _route(xf, router, k_top)
        C = _capacity(T, k_top, m.n_experts, m.capacity_factor)
        buf, combine = _dispatch(xf, gate, idx, C, e0, e_loc,
                                 m.n_experts, k_top)
        out_buf = _expert_ffn(buf, {"w_gate": wg, "w_up": wu, "w_down": wd},
                              act)
        y = combine(out_buf)
        if decode and da:
            y = jax.lax.psum_scatter(y, da, scatter_dimension=0, tiled=True)
            y = jax.lax.psum(y, ep_axis)
            y = y.reshape(B, S, D)
        elif sp:
            y = jax.lax.psum_scatter(y.reshape(B, S, D), ep_axis,
                                     scatter_dimension=1, tiled=True)
        else:
            y = jax.lax.psum(y, ep_axis).reshape(B, S, D)
        aux = _aux_loss(probs, idx, m.n_experts)
        aux = jax.lax.pmean(aux, tuple(mesh.axis_names))
        return y, aux

    y, aux = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(in_spec, P(), wg_spec, wg_spec, wd_spec),
        out_specs=(out_spec, P()),
    )(x, params["router"], params["w_gate"], params["w_up"],
      params["w_down"])
    if "dense" in params:
        from repro.models.modules import mlp
        y = y + mlp(params["dense"], x, cfg.act)
    return y, aux


# ---------------------------------------------------------------------------
# the held share (one device): dropless
# ---------------------------------------------------------------------------
def _held_choices(idx, cfg: ModelConfig, valid):
    """(T, k, held) one-hot of each (token, choice) pair's held expert:
    a zero row where the expert is held elsewhere or the token is not
    ``valid``."""
    m = cfg.moe
    sel = jax.nn.one_hot(idx - m.first_held, m.held, dtype=jnp.float32)
    if valid is not None:
        sel = sel * valid.reshape(-1, 1, 1)
    return sel


def _held_index(idx, cfg: ModelConfig, valid):
    """(T, k) index of each choice among the held experts; -1 where the
    expert is held elsewhere or the token is not ``valid``."""
    e = idx - cfg.moe.first_held
    e = jnp.where((e >= 0) & (e < cfg.moe.held), e, -1)
    return e if valid is None else jnp.where(valid.reshape(-1, 1), e, -1)


def reads_routed(rows: int, cfg: ModelConfig) -> bool:
    """Whether ``moe_held`` on ``rows`` tokens reads only the routed held
    experts (the ``routed_experts`` kernel): their rows·k (token, choice)
    pairs cannot reach every held expert.  Otherwise every row goes
    through every held expert."""
    return rows * cfg.moe.experts_per_token < cfg.moe.held


def _at_layer(params, layer):
    """``params`` whose expert weights are stacked over layers, taken at
    ``layer``; as given where ``layer`` is None."""
    if layer is None:
        return params
    return {n: (jax.lax.dynamic_index_in_dim(v, layer, keepdims=False)
                if n in EXPERT_WEIGHTS else v) for n, v in params.items()}


def held_rows(params, x, cfg: ModelConfig, valid=None):
    """(pairs, routed) of ``moe_held`` on ``x``: the (token, held expert)
    pairs that carry a weight, those of ``valid`` tokens whose top k take
    a held expert, and the ``valid`` tokens times k.  Same routing as the
    layer's, so XLA computes it once."""
    m = cfg.moe
    xf = x.reshape(-1, x.shape[-1])
    _, idx, _ = _route(xf, params["router"], m.experts_per_token)
    rows = jnp.sum(_held_choices(idx, cfg, valid)).astype(jnp.int32)
    n = xf.shape[0] if valid is None else jnp.sum(valid, dtype=jnp.int32)
    return rows, jnp.asarray(n * m.experts_per_token, jnp.int32)


def held_blocks(params, x, cfg: ModelConfig, valid=None):
    """The held experts whose weights ``moe_held`` on ``x`` reads: the
    distinct ones the ``valid`` tokens routed to, or every held expert
    where every row goes through each (``reads_routed``)."""
    m = cfg.moe
    xf = x.reshape(-1, x.shape[-1])
    if not reads_routed(xf.shape[0], cfg):
        return jnp.int32(m.held)
    _, idx, _ = _route(xf, params["router"], m.experts_per_token)
    return blocks_read(_held_index(idx, cfg, valid), m.held)


def moe_held(params, x, cfg: ModelConfig, valid=None, layer=None):
    """The part of the MoE layer that the held experts give, dropless.

    Softmax over the router's ``n_experts``, top-k, the k weights
    renormalised; every token's held choices are computed, none dropped,
    and those of tokens not ``valid`` (B,S) (default: all) carry no
    weight.  ``layer``: where given, the expert weights are stacked over
    layers and it picks this layer's.

    Two forms of one computation, chosen by shape (``reads_routed``):
    where the pairs cannot reach every held expert, the ``routed_experts``
    kernel reads only the routed experts' weights; otherwise each token
    goes through every held expert and each result is weighted by the
    token's gate on that expert, 0 off its top k.  On a TPU v5e that
    dense form beat ``ragged_dot`` over the pairs sorted by expert both
    at one decode token and at a 512-row chunk (PERF.md, section 6)."""
    m = cfg.moe
    B, S, D = x.shape
    xf = x.reshape(B * S, D)
    gate, idx, probs = _route(xf, params["router"], m.experts_per_token)
    act = activation(cfg.act)
    if reads_routed(B * S, cfg):
        y = routed_experts(xf, gate, _held_index(idx, cfg, valid),
                           *(params[n] for n in EXPERT_WEIGHTS), act=act,
                           layer=layer)
    else:
        p = _at_layer(params, layer)
        g = jnp.einsum("tk,tke->te", gate, _held_choices(idx, cfg, valid))
        h = act(jnp.einsum("td,edf->tef", xf, p["w_gate"])) \
            * jnp.einsum("td,edf->tef", xf, p["w_up"])
        h = (h.astype(jnp.float32) * g[:, :, None]).astype(x.dtype)
        y = jnp.einsum("tef,efd->td", h, p["w_down"])
    y = y.reshape(B, S, D)
    if "dense" in params:
        y = y + mlp(params["dense"], x, cfg.act)
    return y, _aux_loss(probs, idx, m.n_experts)


def moe_layer(params, x, cfg: ModelConfig, decode: bool = False,
              valid=None, layer=None):
    """The expert layer: sharded over a mesh where one is set, else the
    held share on this device (``moe_held``), for every caller.
    ``layer``: where given, the expert weights are stacked over layers
    and it picks this layer's."""
    if dctx.current() is not None:
        return moe_sharded(_at_layer(params, layer), x, cfg, decode=decode)
    return moe_held(params, x, cfg, valid, layer)
