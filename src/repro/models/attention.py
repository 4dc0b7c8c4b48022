"""GQA attention: full, chunked (flash-style q-block scan), and decode paths.

Supports sliding windows (mistral/gemma local layers), logit softcaps
(gemma2), qk-norm (gemma3/qwen3), prefix-LM masks (paligemma), bidirectional
encoders and cross-attention (whisper).

The q-block scan keeps activation memory O(S * q_block) instead of O(S^2) so
32k-token prefill lowers without materializing score matrices.  A Pallas
flash-attention kernel (``repro.kernels``) can be swapped in via
``set_attention_impl`` — the jnp path below doubles as its oracle.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN_LOCAL, ModelConfig
from repro.distributed.context import constrain
from repro.models.modules import pdtype, rope, rms_norm

_ATTN_IMPL: Optional[Callable] = None  # pluggable kernel (set by repro.kernels)


def set_attention_impl(fn: Optional[Callable]):
    global _ATTN_IMPL
    _ATTN_IMPL = fn


# ---------------------------------------------------------------------------
# params
# ---------------------------------------------------------------------------
def init_attention(key, cfg: ModelConfig, cross: bool = False) -> dict:
    d, h, k, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    dt = pdtype(cfg)
    ks = jax.random.split(key, 4)
    p = {
        "wq": jax.random.normal(ks[0], (d, h * hd), dt) * d ** -0.5,
        "wk": jax.random.normal(ks[1], (d, k * hd), dt) * d ** -0.5,
        "wv": jax.random.normal(ks[2], (d, k * hd), dt) * d ** -0.5,
        "wo": jax.random.normal(ks[3], (h * hd, d), dt) * (h * hd) ** -0.5,
    }
    if cfg.qk_norm and not cross:
        p["q_norm"] = jnp.zeros((hd,), jnp.float32)
        p["k_norm"] = jnp.zeros((hd,), jnp.float32)
    return p


def _rope(x, positions, cfg: ModelConfig, kind: str):
    """Rotary embedding of a layer: local layers at ``rope_theta``; global
    ones at ``rope_theta_global`` where set, with YaRN where
    ``rope_yarn`` is set."""
    if kind == ATTN_LOCAL:
        return rope(x, positions, cfg.rope_theta)
    return rope(x, positions, cfg.rope_theta_global or cfg.rope_theta,
                cfg.rope_yarn)


# ---------------------------------------------------------------------------
# masking helpers: mask(q_pos, kv_pos) -> bool allow
# ---------------------------------------------------------------------------
def make_mask_fn(mode: str, window: int = 0, prefix_len: int = 0):
    def fn(q_pos, kv_pos):
        q = q_pos[:, None]
        kv = kv_pos[None, :]
        if mode == "bidir":
            allow = jnp.ones(jnp.broadcast_shapes(q.shape, kv.shape), bool)
        elif mode == "prefix":
            causal = kv <= q
            in_prefix = kv < prefix_len
            allow = causal | in_prefix
        else:  # causal
            allow = kv <= q
        if window:
            allow &= kv > q - window
        allow &= kv >= 0
        return allow
    return fn


@jax.custom_vjp
def qk_scores(q, k):
    """f32-accumulated QK^T whose *cotangents* stay in the operand dtype —
    without this, the f32 ds pollutes every upstream gradient (weights
    included), doubling backward-pass memory."""
    return jnp.einsum("bskgd,btkd->bkgst", q, k,
                      preferred_element_type=jnp.float32)


def _qk_fwd(q, k):
    return qk_scores(q, k), (q, k)


def _qk_bwd(res, ds):
    q, k = res
    dq = jnp.einsum("bkgst,btkd->bskgd", ds, k,
                    preferred_element_type=jnp.float32).astype(q.dtype)
    dk = jnp.einsum("bkgst,bskgd->btkd", ds, q,
                    preferred_element_type=jnp.float32).astype(k.dtype)
    return dq, dk


qk_scores.defvjp(_qk_fwd, _qk_bwd)


def _sdpa(q, k, v, mask, softcap: float, scale: float, want_lse: bool = False):
    """q: (B,Sq,K,G,hd)  k,v: (B,Skv,K,hd)  mask: (Sq,Skv) or (B,Sq,Skv)
    or None (dense)."""
    s = qk_scores(q, k) * scale
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    if mask is not None:
        if mask.ndim == 2:
            mask = mask[None]
        s = jnp.where(mask[:, None, None], s, -1e30)
    a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", a, v)
    if want_lse:
        return o, jax.nn.logsumexp(s, axis=-1)     # (B,K,G,Sq)
    return o


def _lse_merge(o1, lse1, o2, lse2):
    """Combine two attention partials over disjoint KV sets."""
    m = jnp.maximum(lse1, lse2)
    w1 = jnp.exp(lse1 - m)
    w2 = jnp.exp(lse2 - m)
    # o: (B,Sq,K,G,hd); lse/w: (B,K,G,Sq) -> align to o
    a1 = w1.transpose(0, 3, 1, 2)[..., None]
    a2 = w2.transpose(0, 3, 1, 2)[..., None]
    o = (o1.astype(jnp.float32) * a1 + o2.astype(jnp.float32) * a2) \
        / (a1 + a2)
    return o.astype(o1.dtype), m + jnp.log(w1 + w2)


def _rect_scan(q, k, v, softcap, scale, qb: int):
    """Dense (unmasked) attention of q against full k/v, scanned over q
    blocks; returns (o, lse).  No masked waste — every MXU flop is useful."""
    B, S, K, G, hd = q.shape
    nb = max(S // qb, 1)
    qb = S // nb
    qblocks = q.reshape(B, nb, qb, K, G, hd).transpose(1, 0, 2, 3, 4, 5)

    def body(_, qi):
        return None, _sdpa(qi, k, v, None, softcap, scale, want_lse=True)

    _, (ob, lseb) = jax.lax.scan(body, None, qblocks, unroll=True)
    o = ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, K, G, hd)
    lse = lseb.transpose(1, 2, 3, 0, 4).reshape(B, K, G, S)
    return o, lse


def _causal_packed(q, k, v, softcap, scale, qb: int, leaf: int):
    """Recursive causal attention with NO masked-rectangle waste:
    attn(S) = [attn(S/2) over first half,
               merge(attn(S/2) over second half diagonal,
                     dense rect(second-half q x first-half kv))].
    HLO flops ~= S^2/2 (exact causal work) instead of the q-block scan's
    ~S^2.  Static shapes at every level (log2 recursion)."""
    B, S, K, G, hd = q.shape
    if S <= leaf or S % 2 != 0:
        mask = make_mask_fn("causal")(jnp.arange(S), jnp.arange(S))
        return _sdpa(q, k, v, mask, softcap, scale, want_lse=True)
    h = S // 2
    o1, lse1 = _causal_packed(q[:, :h], k[:, :h], v[:, :h], softcap, scale,
                              qb, leaf)
    od, lsed = _causal_packed(q[:, h:], k[:, h:], v[:, h:], softcap, scale,
                              qb, leaf)
    orr, lser = _rect_scan(q[:, h:], k[:, :h], v[:, :h], softcap, scale, qb)
    o2, lse2 = _lse_merge(od, lsed, orr, lser)
    return (jnp.concatenate([o1, o2], axis=1),
            jnp.concatenate([lse1, lse2], axis=3))


# ---------------------------------------------------------------------------
# full-sequence attention (train / prefill / encoder)
# ---------------------------------------------------------------------------
def attention_seq(params, x, cfg: ModelConfig, kind: str, positions,
                  mask_mode: str = "causal", prefix_len: int = 0,
                  kv_override=None):
    """x: (B,S,D) -> (B,S,D); also returns (k,v) for cache building.

    ``kv_override=(k_src, kv_positions)`` switches to cross-attention
    (whisper decoder): K/V are projected from the encoder output.
    """
    B, S, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // K
    window = cfg.window_size if kind == ATTN_LOCAL else 0

    q = (x @ params["wq"]).reshape(B, S, H, hd)
    if kv_override is None:
        kv_src, kv_pos = x, positions
    else:
        kv_src, kv_pos = kv_override
    Skv = kv_src.shape[1]
    k = (kv_src @ params["wk"]).reshape(B, Skv, K, hd)
    v = (kv_src @ params["wv"]).reshape(B, Skv, K, hd)

    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        k = rms_norm(k, params["k_norm"], cfg.norm_eps)
    use_rope = not cfg.encoder_decoder
    if use_rope:
        q = _rope(q, positions, cfg, kind)
        k = _rope(k, kv_pos, cfg, kind)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))

    q = q.reshape(B, S, K, G, hd)
    scale = hd ** -0.5
    mask_fn = make_mask_fn(mask_mode, window, prefix_len)

    qb = cfg.attn_q_block
    # causal packing is a net win only when the head dim shards evenly on
    # the model axis — padded heads force GSPMD re-layout collectives on
    # every packed slice (measured on arctic-480b, EXPERIMENTS.md §Perf)
    from repro.distributed import context as _dctx
    _rules = _dctx.current()
    _tp = _rules.mesh.shape.get("model", 1) if _rules is not None else 1
    if cfg.attn_causal_pack == "on":
        pack_ok = True
    elif cfg.attn_causal_pack == "off":
        pack_ok = False
    else:
        pack_ok = cfg.n_heads % max(_tp, 1) == 0

    if _ATTN_IMPL is not None and mask_mode == "causal":
        o = _ATTN_IMPL(q, k, v, window=window, softcap=cfg.attn_logit_softcap,
                       scale=scale)
    elif S <= 2 * qb or S % qb != 0 or kv_override is not None:
        mask = mask_fn(positions[0] if positions.ndim > 1 else positions,
                       kv_pos[0] if kv_pos.ndim > 1 else kv_pos)
        o = _sdpa(q, k, v, mask, cfg.attn_logit_softcap, scale)
    elif mask_mode == "bidir":
        # dense attention scanned over q blocks: O(S*qb) score memory
        # instead of the S^2 monolith (whisper's 32k encoder)
        o, _ = _rect_scan(q, k, v, cfg.attn_logit_softcap, scale, qb)
    elif mask_mode == "causal" and not window and S % (2 * qb) == 0 \
            and pack_ok:
        # causal packing: halves attention HLO flops vs the masked q-block
        # scan (see EXPERIMENTS.md §Perf)
        o, _ = _causal_packed(q, k, v, cfg.attn_logit_softcap, scale, qb,
                              leaf=2 * qb)
    else:
        o = _qblock_scan(q, k, v, mask_fn, cfg.attn_logit_softcap, scale,
                         qb, window)
    o = o.reshape(B, S, H * hd)
    o = constrain(o, ("batch", "seq", "heads_flat"))
    return o @ params["wo"], (k, v)


def _qblock_scan(q, k, v, mask_fn, softcap, scale, qb: int, window: int):
    """Scan over q blocks; local layers slice a static (qb+W) KV window."""
    B, S, K, G, hd = q.shape
    nb = S // qb
    qblocks = q.reshape(B, nb, qb, K, G, hd).transpose(1, 0, 2, 3, 4, 5)

    if window and (qb + window) < S:
        L = qb + window

        def body(_, inp):
            i, qi = inp
            start = jnp.maximum(i * qb - window, 0)
            ks = jax.lax.dynamic_slice_in_dim(k, start, L, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, L, axis=1)
            q_pos = i * qb + jnp.arange(qb)
            kv_pos = start + jnp.arange(L)
            o = _sdpa(qi, ks, vs, mask_fn(q_pos, kv_pos), softcap, scale)
            return None, o
    else:
        def body(_, inp):
            i, qi = inp
            q_pos = i * qb + jnp.arange(qb)
            kv_pos = jnp.arange(S)
            o = _sdpa(qi, k, v, mask_fn(q_pos, kv_pos), softcap, scale)
            return None, o

    # Full unroll: the q-block loop appears explicitly in HLO so
    # cost_analysis counts every block (see roofline methodology).
    _, ob = jax.lax.scan(body, None, (jnp.arange(nb), qblocks),
                         unroll=True)
    return ob.transpose(1, 0, 2, 3, 4, 5).reshape(B, S, K, G, hd)


# ---------------------------------------------------------------------------
# decode (single new token against a cache)
# ---------------------------------------------------------------------------
def init_attn_cache(cfg: ModelConfig, kind: str, batch: int, seq_len: int,
                    dtype=jnp.bfloat16, cross_len: int = 0) -> dict:
    K, hd = cfg.n_kv_heads, cfg.head_dim
    if cross_len:
        return {"ck": jnp.zeros((batch, cross_len, K, hd), dtype),
                "cv": jnp.zeros((batch, cross_len, K, hd), dtype)}
    L = min(cfg.window_size, seq_len) if kind == ATTN_LOCAL else seq_len
    if cfg.kv_quant:
        # int8 KV with per-(token, head) absmax scales: halves HBM traffic
        # of the decode-dominant cache reads
        return {"k": jnp.zeros((batch, L, K, hd), jnp.int8),
                "v": jnp.zeros((batch, L, K, hd), jnp.int8),
                "ksc": jnp.zeros((batch, L, K), jnp.float32),
                "vsc": jnp.zeros((batch, L, K), jnp.float32)}
    return {"k": jnp.zeros((batch, L, K, hd), dtype),
            "v": jnp.zeros((batch, L, K, hd), dtype)}


def _kv_quantize(t):
    """(B,S,K,hd) -> int8 values + (B,S,K) scales."""
    sc = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    sc = jnp.maximum(sc, 1e-8) / 127.0
    q = jnp.clip(jnp.round(t.astype(jnp.float32) / sc[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, sc


def _kv_dequantize(q, sc, dtype=jnp.bfloat16):
    return (q.astype(jnp.float32) * sc[..., None]).astype(dtype)


def attention_decode(params, x, cfg: ModelConfig, kind: str, cache: dict,
                     pos, prefix_len: int = 0, cross: bool = False):
    """x: (B,1,D); cache holds K/V; pos: scalar int32 (current position).

    Returns (out (B,1,D), updated cache).
    """
    B, _, D = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // K
    scale = hd ** -0.5

    q = (x @ params["wq"]).reshape(B, 1, H, hd)
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
    use_rope = not cfg.encoder_decoder

    if cross:
        k, v = cache["ck"], cache["cv"]
        Skv = k.shape[1]
        s = jnp.einsum("bskgd,btkd->bkgst", q.reshape(B, 1, K, G, hd), k,
                       preferred_element_type=jnp.float32) * scale
        a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        o = jnp.einsum("bkgst,btkd->bskgd", a, v).reshape(B, 1, H * hd)
        return o @ params["wo"], cache

    kn = (x @ params["wk"]).reshape(B, 1, K, hd)
    vn = (x @ params["wv"]).reshape(B, 1, K, hd)
    if "k_norm" in params:
        kn = rms_norm(kn, params["k_norm"], cfg.norm_eps)
    posv = jnp.full((1,), pos, jnp.int32)
    if use_rope:
        q = _rope(q, posv[None, :], cfg, kind)
        kn = _rope(kn, posv[None, :], cfg, kind)
    quant = "ksc" in cache
    if quant:
        kn_q, kn_s = _kv_quantize(kn)
        vn_q, vn_s = _kv_quantize(vn)

    L = cache["k"].shape[1]
    ring = kind == ATTN_LOCAL and cfg.window_size and L <= cfg.window_size
    idx = jnp.mod(pos, L) if ring else pos
    dus = jax.lax.dynamic_update_slice_in_dim
    new_cache = dict(cache)
    if quant:
        new_cache["k"] = dus(cache["k"], kn_q, idx, axis=1)
        new_cache["v"] = dus(cache["v"], vn_q, idx, axis=1)
        new_cache["ksc"] = dus(cache["ksc"], kn_s, idx, axis=1)
        new_cache["vsc"] = dus(cache["vsc"], vn_s, idx, axis=1)
        k = _kv_dequantize(new_cache["k"], new_cache["ksc"], x.dtype)
        v = _kv_dequantize(new_cache["v"], new_cache["vsc"], x.dtype)
    else:
        new_cache["k"] = k = dus(cache["k"], kn, idx, axis=1)
        new_cache["v"] = v = dus(cache["v"], vn, idx, axis=1)
    if ring:
        slot = jnp.arange(L)
        kv_pos = pos - jnp.mod(idx - slot, L)          # absolute positions
        allow = kv_pos >= 0
    else:
        kv_pos = jnp.arange(L)
        allow = kv_pos <= pos
        if kind == ATTN_LOCAL:
            allow &= kv_pos > pos - cfg.window_size
        if prefix_len:
            allow |= kv_pos < prefix_len

    s = jnp.einsum("bskgd,btkd->bkgst", q.reshape(B, 1, K, G, hd), k,
                   preferred_element_type=jnp.float32) * scale
    if cfg.attn_logit_softcap:
        s = cfg.attn_logit_softcap * jnp.tanh(s / cfg.attn_logit_softcap)
    s = jnp.where(allow[None, None, None, None, :], s, -1e30)
    a = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", a, v).reshape(B, 1, H * hd)
    return o @ params["wo"], new_cache


# ---------------------------------------------------------------------------
# prefill chunk (C tokens of one lane against that lane's cache)
# ---------------------------------------------------------------------------
def _write_rows(buf, rows, lane, s0, last, ring: bool):
    """Write ``rows`` (1,C,...) into lane ``lane`` of ``buf`` (B,L,...):
    row j goes to slot (s0 + j) mod L on a ring, else to slot s0 + j (rows
    past L are dropped); rows past ``last`` are never written.

    Two read-modify-write windows of C slots, each a dynamic slice: the
    first ends at the ring's end or starts at s0, the second starts at
    slot 0 and takes the rows that wrapped (none off the ring)."""
    C, L = rows.shape[1], buf.shape[1]
    i = jnp.arange(C)
    tail = (slice(None),) + (None,) * (rows.ndim - 2)
    n1 = jnp.minimum(C, L - s0)                  # rows before the ring's end
    zeros = jnp.zeros_like(rows)

    def rmw(buf, start, shifted, valid):
        idx = (lane, start) + (0,) * (rows.ndim - 2)
        old = jax.lax.dynamic_slice(buf, idx, rows.shape)
        new = jnp.where(valid[tail][None], shifted, old)
        return jax.lax.dynamic_update_slice(buf, new, idx)

    st = jnp.minimum(s0, L - C)
    d = st - s0                                  # in (-C, 0]
    shifted = jax.lax.dynamic_slice_in_dim(
        jnp.concatenate([zeros, rows], axis=1), C + d, C, axis=1)
    j = i + d
    buf = rmw(buf, st, shifted, (j >= 0) & (j < n1) & (j <= last))
    if ring:
        shifted = jax.lax.dynamic_slice_in_dim(
            jnp.concatenate([rows, zeros], axis=1), n1, C, axis=1)
        j = i + n1
        buf = rmw(buf, jnp.zeros_like(st), shifted, (j < C) & (j <= last))
    return buf


def attention_chunk(params, x, cfg: ModelConfig, kind: str, cache: dict,
                    positions, last, lane):
    """x: (1,C,D), one lane's prompt tokens at ``positions`` (C,) =
    pos..pos+C-1; cache holds K/V (B,L,K,hd), unquantised (the chunk
    rule is ``transformer.prefill_chunk_len``).

    Partials merged by log-sum-exp: the chunk's queries against its own
    K/V, causal, then against the lane's cache before this chunk is
    written (positions < pos; on a ring the absolute positions follow
    from pos as in ``attention_decode``), C slots at a time, where a block
    of slots that no query may see is skipped.  The window mask applies
    to both.  Then rows 0..``last`` of the chunk's K/V go into the lane's
    slots; a ring stays right when it wraps inside a prompt as long as
    C <= L.  Returns (out (1,C,D), updated cache)."""
    _, C, _ = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    G = H // K
    window = cfg.window_size if kind == ATTN_LOCAL else 0
    scale = hd ** -0.5
    softcap = cfg.attn_logit_softcap
    mask_fn = make_mask_fn("causal", window)
    pos = positions[0]

    q = (x @ params["wq"]).reshape(1, C, H, hd)
    kn = (x @ params["wk"]).reshape(1, C, K, hd)
    vn = (x @ params["wv"]).reshape(1, C, K, hd)
    if "q_norm" in params:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        kn = rms_norm(kn, params["k_norm"], cfg.norm_eps)
    q = _rope(q, positions[None], cfg, kind).reshape(1, C, K, G, hd)
    kn = _rope(kn, positions[None], cfg, kind)
    o, lse = _sdpa(q, kn, vn, mask_fn(positions, positions), softcap, scale,
                   want_lse=True)
    o = o.astype(jnp.float32)

    L = cache["k"].shape[1]
    ring = bool(window) and L <= window
    slot = jnp.arange(L)
    if ring:
        prev = pos - 1                           # the newest cached position
        kv_pos = prev - jnp.mod(jnp.mod(prev, L) - slot, L)
    else:
        kv_pos = jnp.where(slot < pos, slot, -1)
    old_k = jax.lax.dynamic_slice_in_dim(cache["k"], lane, 1, axis=0)
    old_v = jax.lax.dynamic_slice_in_dim(cache["v"], lane, 1, axis=0)
    for b in range(0, L, C):
        blk = slice(b, min(b + C, L))
        mask = mask_fn(positions, kv_pos[blk])

        def merge(acc, kb=old_k[:, blk], vb=old_v[:, blk], mask=mask):
            ob, lseb = _sdpa(q, kb, vb, mask, softcap, scale, want_lse=True)
            return _lse_merge(*acc, ob, lseb)
        o, lse = jax.lax.cond(mask.any(), merge, lambda acc: acc, (o, lse))

    s0 = jnp.mod(pos, L) if ring else pos
    new_cache = dict(cache)
    new_cache["k"] = _write_rows(cache["k"], kn.astype(cache["k"].dtype),
                                 lane, s0, last, ring)
    new_cache["v"] = _write_rows(cache["v"], vn.astype(cache["v"].dtype),
                                 lane, s0, last, ring)
    o = o.astype(x.dtype).reshape(1, C, H * hd)
    return o @ params["wo"], new_cache


def build_ring_cache(k_full, v_full, window: int, quant: bool = False):
    """Convert full prefill K/V (B,S,K,hd) into the decode ring layout."""
    S = k_full.shape[1]
    if S > window:
        idx = (S - 1) % window
        slot = jnp.arange(window)
        p = (S - 1) - jnp.mod(idx - slot, window)
        k_full = jnp.take(k_full, p, axis=1)
        v_full = jnp.take(v_full, p, axis=1)
    return pack_kv(k_full, v_full, quant)


def pack_kv(k, v, quant: bool = False) -> dict:
    if not quant:
        return {"k": k, "v": v}
    kq, ks = _kv_quantize(k)
    vq, vs = _kv_quantize(v)
    return {"k": kq, "v": vq, "ksc": ks, "vsc": vs}
