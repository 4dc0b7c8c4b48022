"""Plain reference of the flood-detection chain (arXiv 2508.15351, Fig. 4).

    ingest: Laplacian (3x3, zero padding) of each frame; sharpness is the
            variance of it; frames at or below the 20th percentile of
            sharpness (linear interpolation) are zeroed, the rest kept.
    detect: two 3x3 convolutions with stride 2 and "SAME" padding
            (1 -> 8 channels, ReLU, 8 -> 4), mean over space and
            channels, sigmoid: one score per frame.
    map:    the SAR tile it is handed, else a tile of ones (8, 64, 64);
            one 5x5 convolution (1 -> 4, "SAME"), sigmoid of the channel
            mean; the detections pass through.
    alarm:  score = mean(detections) + mean(flood map); alarm = score > 0.5.

Each stage's input is what the workflow's edges give it: detect gets
ingest's output, map gets detect's, alarm gets map's.  So map never sees
the SAR tile of the workflow's input and maps a tile of ones.

The convolutions' weights are standard normal draws times 0.1, from
``jax.random.PRNGKey(7)`` (both of detect's, from the same key) and
``PRNGKey(13)`` (map's), in HWIO layout; the reference draws them itself.

Convolutions are written as sums of shifted slices, in float32 with
every product exact (``Precision.HIGHEST``).  ``dtype=jnp.bfloat16`` is
the control: the same chain with inputs, weights and every stage held in
bfloat16, as a program whose bodies were cast to bfloat16 would hold
them (XLA may keep float32 where a value is converted back).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
_LAPLACE = ((0, 1, 0), (1, -4, 1), (0, 1, 0))


def _conv(x, w, stride: int, pad):
    """x: (N, H, W, Cin); w: (kh, kw, Cin, Cout); explicit padding."""
    kh, kw = w.shape[:2]
    x = jnp.pad(x, ((0, 0), pad[0], pad[1], (0, 0)))
    H = (x.shape[1] - kh) // stride + 1
    W = (x.shape[2] - kw) // stride + 1
    out = 0.0
    for i in range(kh):
        for j in range(kw):
            win = x[:, i:i + stride * (H - 1) + 1:stride,
                    j:j + stride * (W - 1) + 1:stride, :]
            out = out + jnp.einsum("nhwc,cd->nhwd", win, w[i, j],
                                   precision=HI)
    return out


def _same(size: int, k: int, stride: int):
    out = -(-size // stride)
    total = max((out - 1) * stride + k - size, 0)
    return (total // 2, total - total // 2)


@partial(jax.jit, static_argnames=("dtype",))
def chain(frames, *, dtype=jnp.float32):
    """Every stage's output for one input's frames."""
    f = frames.astype(dtype)
    N, H, W = f.shape
    lap = jnp.zeros_like(f)
    fp = jnp.pad(f, ((0, 0), (1, 1), (1, 1)))
    for i in range(3):
        for j in range(3):
            if _LAPLACE[i][j]:
                lap = lap + jnp.asarray(_LAPLACE[i][j], dtype) \
                    * fp[:, i:i + H, j:j + W]
    flat = lap.reshape(N, -1)
    mean = jnp.mean(flat, axis=1, keepdims=True)
    sharp = jnp.mean((flat - mean) ** 2, axis=1)
    srt = jnp.sort(sharp)
    rank = 0.2 * (N - 1)
    lo = int(rank)
    thr = srt[lo] + (srt[min(lo + 1, N - 1)] - srt[lo]) * (rank - lo)
    keep = sharp > thr
    kept = f * keep[:, None, None].astype(dtype)

    key7 = jax.random.PRNGKey(7)
    w1 = (jax.random.normal(key7, (3, 3, 1, 8), jnp.float32) * 0.1
          ).astype(dtype)
    w2 = (jax.random.normal(key7, (3, 3, 8, 4), jnp.float32) * 0.1
          ).astype(dtype)
    x = _conv(kept[..., None], w1, 2, (_same(H, 3, 2), _same(W, 3, 2)))
    x = jnp.maximum(x, 0)
    x = _conv(x, w2, 2, (_same(x.shape[1], 3, 2), _same(x.shape[2], 3, 2)))
    det = jax.nn.sigmoid(jnp.mean(x, axis=(1, 2, 3)))

    w3 = (jax.random.normal(jax.random.PRNGKey(13), (5, 5, 1, 4),
                            jnp.float32) * 0.1).astype(dtype)
    tile = jnp.ones((8, 64, 64), dtype)
    y = _conv(tile[..., None], w3, 1, (_same(64, 5, 1), _same(64, 5, 1)))
    flood = jax.nn.sigmoid(jnp.mean(y, axis=-1))
    score = jnp.mean(det) + jnp.mean(flood)
    return {"keep": keep, "frames": kept.astype(jnp.float32),
            "detections": det.astype(jnp.float32),
            "flood_map": flood.astype(jnp.float32),
            "score": score.astype(jnp.float32)}
