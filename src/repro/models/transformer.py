"""Model assembly: embeddings, layer stacks (scan over superblocks), heads.

One code path serves all ten assigned architectures; the per-layer "kind"
(global/local attention, RG-LRU, RWKV) comes from ``cfg.layer_pattern``.
Layers are stacked as ``n_superblocks`` repetitions of the pattern scanned
with ``lax.scan`` (compact HLO at 94 layers) plus an unrolled tail for
non-divisible depths.

One layer body, ``_layer``, and one walk over the superblocks and the
tail, ``_walk``, serve the four forward entry points, each with a mode:
``Seq`` (train, prefill, the encoder), ``Decode`` or ``Chunk``.  The
mode picks the token mixer (``_mix``) and what follows from it: the cache
entry, cross-attention's source, the expert layer's valid rows and
counters, and the whole expert stacks decode hands its layers.

Entry points:
    init_params(key, cfg)                        -> params pytree
    forward_train(params, cfg, batch)            -> (logits, aux_loss)
    forward_prefill(params, cfg, batch)          -> (logits, cache)
    forward_decode(params, cfg, cache, tok, pos) -> (logits, cache)
    forward_chunk(params, cfg, cache, tok, pos, last, lane) -> (logits, cache)
    prefill_chunk_len(cfg, max_len)              -> chunk length, 0 for none
    init_cache(cfg, batch, seq_len)              -> cache pytree
    expert_counts(cache)                         -> {counter: int}
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ATTN_GLOBAL, ATTN_LOCAL, RGLRU, RWKV, ModelConfig
from repro.distributed.context import constrain
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import rglru as rglru_mod
from repro.models import rwkv as rwkv_mod
from repro.models.modules import (init_mlp, init_norm, mlp, pdtype, rms_norm,
                                  sinusoidal_pos_emb)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def _init_layer(key, cfg: ModelConfig, kind: str, cross: bool = False) -> dict:
    ks = jax.random.split(key, 6)
    p = {"ln1": init_norm(ks[0], cfg.d_model),
         "ln2": init_norm(ks[1], cfg.d_model)}
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        p["attn"] = attn.init_attention(ks[2], cfg)
        if cfg.moe is not None:
            p["moe"] = moe_mod.init_moe(ks[3], cfg)
        else:
            p["mlp"] = init_mlp(ks[3], cfg)
        if cfg.post_norms:
            p["post_ln1"] = init_norm(ks[4], cfg.d_model)
            p["post_ln2"] = init_norm(ks[5], cfg.d_model)
    elif kind == RGLRU:
        p["rec"] = rglru_mod.init_rglru(ks[2], cfg)
        p["mlp"] = init_mlp(ks[3], cfg)
    elif kind == RWKV:
        p["tm"] = rwkv_mod.init_time_mix(ks[2], cfg)
        p["cm"] = rwkv_mod.init_channel_mix(ks[3], cfg)
    else:
        raise ValueError(kind)
    if cross:
        p["ln_x"] = init_norm(ks[4], cfg.d_model)
        p["xattn"] = attn.init_attention(ks[5], cfg, cross=True)
    return p


def init_params(key, cfg: ModelConfig) -> dict:
    dt = pdtype(cfg)
    ks = jax.random.split(key, 8)
    cross = cfg.encoder_decoder
    params = {
        "embed": jax.random.normal(ks[0], (cfg.vocab_size, cfg.d_model), dt)
        * 0.02,
        "final_norm": init_norm(ks[1], cfg.d_model),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            ks[2], (cfg.vocab_size, cfg.d_model), dt) * 0.02

    # scanned superblocks: tuple over pattern positions, leaves (R, ...)
    R = cfg.n_superblocks
    bk = jax.random.split(ks[3], max(R, 1) * cfg.pattern_len)
    blocks = []
    for j, kind in enumerate(cfg.layer_pattern):
        reps = [_init_layer(bk[i * cfg.pattern_len + j], cfg, kind, cross)
                for i in range(R)]
        blocks.append(jax.tree.map(lambda *xs: jnp.stack(xs), *reps))
    params["blocks"] = tuple(blocks)
    tk = jax.random.split(ks[4], max(cfg.n_tail, 1))
    params["tail"] = tuple(
        _init_layer(tk[i], cfg, kind, cross)
        for i, kind in enumerate(cfg.tail_pattern))

    if cfg.encoder_decoder:
        ek = jax.random.split(ks[5], cfg.n_enc_layers + 1)
        params["enc_blocks"] = tuple(
            _init_layer(ek[i], cfg, ATTN_GLOBAL) for i in range(cfg.n_enc_layers))
        params["enc_norm"] = init_norm(ek[-1], cfg.d_model)
    if cfg.frontend:
        params["frontend_proj"] = jax.random.normal(
            ks[6], (cfg.d_model, cfg.d_model), dt) * cfg.d_model ** -0.5
    return params


# ---------------------------------------------------------------------------
# one layer, in every mode
# ---------------------------------------------------------------------------
class Seq(NamedTuple):
    """Train, prefill and the encoder: the whole sequence at once."""
    positions: jax.Array                  # (B,S)
    mask_mode: str
    prefix_len: int
    enc_out: Optional[tuple] = None       # (encoder output, its positions)
    want_cache: bool = False
    seq_exact: bool = False


class Decode(NamedTuple):
    """One token a lane at position ``pos``, against the cache."""
    pos: jax.Array
    prefix_len: int


class Chunk(NamedTuple):
    """C prompt tokens of lane ``lane`` at ``positions`` (C,), against
    that lane's cache; rows past ``last`` are padding."""
    positions: jax.Array
    last: jax.Array
    lane: jax.Array


def _mix(p, h, cfg: ModelConfig, kind: str, mode, cache):
    """The token mixer of a ``kind`` layer in ``mode``: (out, cache
    entry).  In ``Seq`` an attention layer's entry is its full K/V, which
    ``_layer`` packs once the layer is done."""
    seq = isinstance(mode, Seq)
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        if seq:
            o, (k, v) = attn.attention_seq(p["attn"], h, cfg, kind,
                                           mode.positions, mode.mask_mode,
                                           mode.prefix_len)
            return o, {"k": k, "v": v}
        if isinstance(mode, Decode):
            return attn.attention_decode(p["attn"], h, cfg, kind, cache,
                                         mode.pos, mode.prefix_len)
        return attn.attention_chunk(p["attn"], h, cfg, kind, cache,
                                    mode.positions, mode.last, mode.lane)
    if kind == RGLRU:
        if seq:
            o, h_last, conv_tail = rglru_mod.rglru_seq(p["rec"], h, cfg)
            return o, {"h": h_last, "conv": conv_tail}
        return rglru_mod.rglru_decode(p["rec"], h, cfg, cache)
    if kind == RWKV:
        if seq:
            fn = rwkv_mod.wkv_scan if mode.seq_exact else rwkv_mod.wkv_chunked
            o, st, tm_x = fn(p["tm"], h, cfg)
        else:
            o, st, tm_x = rwkv_mod.time_mix_decode(p["tm"], h, cfg, cache)
        return o, {"state": st, "tm_x": tm_x}
    raise ValueError(kind)


def _layer(p, x, cfg: ModelConfig, kind: str, mode, cache=None, layer=None):
    """One layer of any kind in any mode: (x, aux loss, cache entry).

    Pre-norm, token mixer (``_mix``), ``post_ln1``, residual; cross-
    attention where ``ln_x`` is present; ``ln2``, the FFN (experts with
    their counters, RWKV's channel mix or the MLP), ``post_ln2``,
    residual.  ``cache``: the layer's entry, in decode and chunk.
    ``layer``: where given, the expert weights in ``p["moe"]`` are stacked
    over layers and it picks this layer's."""
    seq, decode = isinstance(mode, Seq), isinstance(mode, Decode)
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    o, entry = _mix(p, h, cfg, kind, mode, cache)
    if "post_ln1" in p:
        o = rms_norm(o, p["post_ln1"], cfg.norm_eps)
    x = x + o
    if "ln_x" in p:  # whisper decoder cross-attention
        hx = rms_norm(x, p["ln_x"], cfg.norm_eps)
        if seq:
            ox, (ck, cv) = attn.attention_seq(
                p["xattn"], hx, cfg, ATTN_GLOBAL, mode.positions, "bidir",
                kv_override=mode.enc_out)
            entry.update(ck=ck, cv=cv)
        else:
            ox, _ = attn.attention_decode(p["xattn"], hx, cfg, ATTN_GLOBAL,
                                          cache, mode.pos, cross=True)
        x = x + ox
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if "moe" in p:
        valid = None if seq or decode else \
            (jnp.arange(x.shape[1]) <= mode.last)[None]
        f, aux = moe_mod.moe_layer(p["moe"], h2, cfg, decode=decode,
                                   valid=valid, layer=layer)
        if cache is not None and "experts" in cache:
            entry["experts"] = _count_experts(
                cache["experts"], int(decode),
                *moe_mod.held_rows(p["moe"], h2, cfg, valid),
                moe_mod.held_blocks(p["moe"], h2, cfg) if decode else None)
    elif "cm" in p:
        f, entry["cm_x"] = rwkv_mod.channel_mix(
            p["cm"], h2, None if seq else cache["cm_x"])
    else:
        f = mlp(p["mlp"], h2, cfg.act)
    if "post_ln2" in p:
        f = rms_norm(f, p["post_ln2"], cfg.norm_eps)
    x = x + f
    if seq:
        if not mode.want_cache:
            entry = {}
        elif kind in (ATTN_GLOBAL, ATTN_LOCAL):
            k, v = entry.pop("k"), entry.pop("v")
            entry.update(
                attn.build_ring_cache(k, v, cfg.window_size, cfg.kv_quant)
                if kind == ATTN_LOCAL and cfg.window_size
                else attn.pack_kv(k, v, cfg.kv_quant))
        # sequence-parallel residual boundary: the scan carry (and the
        # per-layer activation checkpoints it implies for backward) stays
        # sharded over the model axis, Megatron-SP style.
        x = constrain(x, ("batch", "act_seq", None))
    return x, aux, entry


# ---------------------------------------------------------------------------
# the layer walk
# ---------------------------------------------------------------------------
def _remat_wrap(fn, cfg: ModelConfig):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    return jax.checkpoint(
        fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def _walk(params, x, cfg: ModelConfig, mode, cache=None, remat=False):
    """The scanned superblocks, then the unrolled tail, in ``mode``:
    (x, aux, cache).  ``aux`` sums the layers' aux losses in ``Seq`` and
    is None otherwise; ``cache`` holds each layer's entry."""
    aux0 = jnp.zeros((), jnp.float32) if isinstance(mode, Seq) else None
    blocks, stacks = params["blocks"], None
    if isinstance(mode, Decode) and cfg.moe is not None:
        blocks, stacks = _split_experts(blocks)

    def run(kinds, x, aux, ps, cs, layer=None):
        """Layers ``kinds`` with parameters ``ps`` and cache entries
        ``cs``; ``layer``: the superblock's index, where decode hands
        the expert layers the whole stacks."""
        caches = []
        for j, kind in enumerate(kinds):
            p, i = ps[j], None
            if layer is not None and "moe" in p:
                p, i = {**p, "moe": {**p["moe"], **stacks[j]}}, layer
            x, a, c = _layer(p, x, cfg, kind, mode,
                             None if cs is None else cs[j], i)
            aux = None if aux is None else aux + a
            caches.append(c)
        return x, aux, tuple(caches)

    def block_fn(carry, xs):
        x, aux, caches = run(cfg.layer_pattern, *carry, *xs)
        return (x, aux), caches

    fn = _remat_wrap(block_fn, cfg) if remat else block_fn
    if cfg.n_superblocks > 0:
        xs = (blocks, None if cache is None else cache["blocks"])
        if stacks is not None:
            xs += (jnp.arange(cfg.n_superblocks, dtype=jnp.int32),)
        (x, aux), block_caches = jax.lax.scan(fn, (x, aux0), xs)
    else:
        block_caches, aux = (), aux0
    x, aux, tail = run(cfg.tail_pattern, x, aux, params["tail"],
                       None if cache is None else cache["tail"])
    return x, aux, {"blocks": block_caches, "tail": tail}


def _split_experts(blocks):
    """(the scanned blocks without their expert weights, each pattern
    position's expert weight stacks).  Decode hands the expert layer the
    whole stacks and the layer's index: a Pallas operand sliced out of a
    stack per layer would first be copied out whole, every layer."""
    rest, stacks = [], []
    for bp in blocks:
        moe = bp.get("moe", {})
        stacks.append({n: moe[n] for n in moe_mod.EXPERT_WEIGHTS if n in moe})
        if moe:
            bp = {**bp, "moe": {n: v for n, v in moe.items()
                                if n not in moe_mod.EXPERT_WEIGHTS}}
        rest.append(bp)
    return tuple(rest), tuple(stacks)


def _embed(params, cfg: ModelConfig, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embedding_scale:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


def _unembed(params, cfg: ModelConfig, x):
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    w = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,vd->bsv", x, w)
    if cfg.final_logit_softcap:
        c = cfg.final_logit_softcap
        logits = (c * jnp.tanh(logits.astype(jnp.float32) / c)).astype(
            logits.dtype)
    return constrain(logits, ("batch", None, "vocab"))


def _encode(params, cfg: ModelConfig, frames):
    """Whisper encoder over precomputed frame embeddings (B,S,D)."""
    B, S, D = frames.shape
    x = frames.astype(pdtype(cfg)) @ params["frontend_proj"]
    x = x + sinusoidal_pos_emb(S, D, x.dtype)[None]
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    for p in params["enc_blocks"]:
        x, _, _ = _layer(p, x, cfg, ATTN_GLOBAL, Seq(pos, "bidir", 0))
    return rms_norm(x, params["enc_norm"], cfg.norm_eps), pos


def _prep_inputs(params, cfg: ModelConfig, batch, **seq):
    """Embedding + frontend stub handling -> (x, ``Seq`` mode with
    ``seq``'s fields)."""
    tokens = batch["tokens"]
    B = tokens.shape[0]
    enc_out = None
    mask_mode, prefix_len = "causal", 0
    if cfg.encoder_decoder:
        enc_out = _encode(params, cfg, batch["frames"])
        x = _embed(params, cfg, tokens)
        S = tokens.shape[1]
        x = x + sinusoidal_pos_emb(S, cfg.d_model, x.dtype)[None]
    elif cfg.frontend == "vision":
        patches = batch["patches"].astype(pdtype(cfg)) @ params["frontend_proj"]
        x_txt = _embed(params, cfg, tokens)
        x = jnp.concatenate([patches, x_txt], axis=1)
        mask_mode, prefix_len = "prefix", cfg.n_prefix_tokens
    else:
        x = _embed(params, cfg, tokens)
    S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    x = constrain(x, ("batch", "act_seq", None))
    return x, Seq(positions, mask_mode, prefix_len, enc_out, **seq)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------
def forward_train(params, cfg: ModelConfig, batch, seq_exact: bool = False):
    x, mode = _prep_inputs(params, cfg, batch, seq_exact=seq_exact)
    x, aux, _ = _walk(params, x, cfg, mode, remat=True)
    return _unembed(params, cfg, x), aux


def forward_prefill(params, cfg: ModelConfig, batch):
    x, mode = _prep_inputs(params, cfg, batch, want_cache=True)
    x, _, cache = _walk(params, x, cfg, mode)
    return _unembed(params, cfg, x[:, -1:]), cache


def forward_decode(params, cfg: ModelConfig, cache, tokens, pos):
    """tokens: (B,1); pos: scalar int32; cache from init_cache/prefill."""
    x = _embed(params, cfg, tokens)
    if cfg.encoder_decoder:
        x = x + _sin_at(pos, cfg.d_model, x.dtype)
    x = constrain(x, ("batch", "seq", None))
    x, _, cache = _walk(params, x, cfg, Decode(pos, cfg.n_prefix_tokens),
                        cache)
    return _unembed(params, cfg, x), cache


PREFILL_CHUNK = 512      # prompt tokens a chunk: compute-bound on a v5e


def prefill_chunk_len(cfg: ModelConfig, max_len: int) -> int:
    """The chunk length C for ``cfg`` (``forward_chunk``), or 0 where a
    prompt is fed a token at a time through ``forward_decode``.

    Chunks take stacks of global and local attention layers, with dense
    MLPs or experts, and an unquantised cache, without an encoder or a
    prefix frontend.  Any other configuration (recurrent layers, whose
    chunk would have to scan from the cache's state; an int8 cache;
    whisper; paligemma) gets 0.  C = min(``PREFILL_CHUNK``, ``max_len``,
    every local layer's ring), so a chunk's slots are distinct even where
    the ring wraps inside it."""
    kinds = set(cfg.layer_pattern) | set(cfg.tail_pattern)
    if not kinds <= {ATTN_GLOBAL, ATTN_LOCAL} or cfg.kv_quant \
            or cfg.encoder_decoder or cfg.frontend or cfg.n_prefix_tokens:
        return 0
    c = min(PREFILL_CHUNK, max_len)
    if ATTN_LOCAL in kinds:
        c = min(c, cfg.window_size)
    return c


def forward_chunk(params, cfg: ModelConfig, cache, tokens, pos, last, lane):
    """C prompt tokens of lane ``lane`` at positions pos..pos+C-1, against
    that lane's cache, for the configurations ``prefill_chunk_len`` gives
    a chunk length.

    tokens: (1,C); pos, last, lane: int32 scalars.  Rows past ``last``
    are padding: computed, never written to the cache, and with gates of 0
    in an expert layer.  Returns the logits of row ``last`` only, (1,1,V),
    and the cache with the chunk's K/V in the lane's slots; every other
    lane is left as it was."""
    x = _embed(params, cfg, tokens)
    positions = pos + jnp.arange(tokens.shape[1], dtype=jnp.int32)
    x, _, cache = _walk(params, x, cfg, Chunk(positions, last, lane), cache)
    logits = _unembed(params, cfg,
                      jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1))
    return logits, cache


def _sin_at(pos, d, dtype):
    i = jnp.arange(d // 2)
    ang = pos.astype(jnp.float32) / jnp.power(10_000.0, 2 * i / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)])[None, None].astype(dtype)


# ---------------------------------------------------------------------------
# expert counters: in an MoE layer's cache entry from ``init_cache``, rows
# of 64-bit counts held as (low, high) uint32 words, added to inside the
# chunk and decode programs
# ---------------------------------------------------------------------------
EXPERT_COUNTS = ("expert_rows_prefill", "expert_rows_decode",
                 "expert_pairs_routed", "expert_blocks_decode")


def _count_experts(counts, row: int, rows, routed, blocks=None):
    """Add ``rows`` (token, held expert) pairs computed to counter ``row``,
    ``routed`` pairs to ``expert_pairs_routed`` and, where given,
    ``blocks`` held experts read to ``expert_blocks_decode``."""
    add = jnp.zeros((len(EXPERT_COUNTS),), jnp.uint32)
    add = add.at[row].set(rows.astype(jnp.uint32))
    add = add.at[2].set(routed.astype(jnp.uint32))
    if blocks is not None:
        add = add.at[3].set(blocks.astype(jnp.uint32))
    lo = counts[:, 0] + add
    return jnp.stack([lo, counts[:, 1] + (lo < add).astype(jnp.uint32)], 1)


def expert_counts(cache) -> dict:
    """The expert counters of ``cache`` summed over its layers, as ints
    (one device-to-host copy); empty where the cache has none."""
    leaves = [c["experts"] for c in (*cache["blocks"], *cache["tail"])
              if "experts" in c]
    if not leaves:
        return {}
    words = np.concatenate([np.asarray(t).reshape(-1, len(EXPERT_COUNTS), 2)
                            for t in jax.device_get(leaves)])
    words = words.astype(np.uint64)
    total = (words[..., 0] + (words[..., 1] << np.uint64(32))).sum(0)
    return {k: int(v) for k, v in zip(EXPERT_COUNTS, total)}


# ---------------------------------------------------------------------------
# cache init (zeros; decode-from-scratch or dry-run stand-in)
# ---------------------------------------------------------------------------
def _layer_cache(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                 dtype, cross_len: int = 0):
    if kind in (ATTN_GLOBAL, ATTN_LOCAL):
        c = attn.init_attn_cache(cfg, kind, batch, seq_len, dtype)
        if cfg.encoder_decoder:
            c.update(attn.init_attn_cache(cfg, kind, batch, seq_len, dtype,
                                          cross_len=cross_len))
        if cfg.moe is not None:
            c["experts"] = jnp.zeros((len(EXPERT_COUNTS), 2), jnp.uint32)
        return c
    if kind == RGLRU:
        return rglru_mod.init_rglru_cache(cfg, batch, dtype)
    if kind == RWKV:
        return rwkv_mod.init_rwkv_cache(cfg, batch, dtype)
    raise ValueError(kind)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int,
               dtype=jnp.bfloat16) -> dict:
    cross_len = seq_len if cfg.encoder_decoder else 0
    dec_len = seq_len // cfg.dec_len_ratio if cfg.encoder_decoder else seq_len
    R = cfg.n_superblocks
    blocks = []
    for j, kind in enumerate(cfg.layer_pattern):
        one = _layer_cache(cfg, kind, batch, dec_len, dtype, cross_len)
        blocks.append(jax.tree.map(
            lambda t: jnp.zeros((R,) + t.shape, t.dtype), one)
            if R else one)
    tail = tuple(_layer_cache(cfg, kind, batch, dec_len, dtype, cross_len)
                 for kind in cfg.tail_pattern)
    return {"blocks": tuple(blocks), "tail": tail}
