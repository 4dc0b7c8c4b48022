"""The one traffic generator: a mix's data file in, a run's inputs out.

A serving mix (``perfbench/traffic/<name>.json``, ``"kind": "serving"``)
lists ``[prompt_len, max_new]`` pairs.  Every seed walks that list in the
same order, cycling, so each window holds the same mix of lengths; the
seed draws only the token ids, from ``[2, vocab)``.

A workflow mix (``"kind": "workflow"``) gives the size of the input pool
and the frames' contrasts: ``payloads`` inputs are made from the seed on
the device, in the shapes the configuration states, and instances take
them in turn.  Which instances are checked is drawn from the seed too
(``Sampler``).
"""
from __future__ import annotations

import itertools
from typing import Dict, Iterator, List, Tuple

import numpy as np

# separate streams of one seed
_TOKENS, _SAMPLE, _PAYLOAD = 1, 2, 3


def serving_requests(mix: Dict, vocab: int,
                     seed: int) -> Iterator[Tuple[np.ndarray, int]]:
    """Endless (prompt ids, max_new) pairs, lengths in the mix's order."""
    if mix.get("kind") != "serving":
        raise ValueError(f"not a serving mix: {mix.get('kind')!r}")
    rng = np.random.default_rng([seed, _TOKENS])
    for P, max_new in itertools.cycle(mix["requests"]):
        yield rng.integers(2, vocab, size=P, dtype=np.int32), int(max_new)


def workflow_payloads(mix: Dict, config: Dict, seed: int) -> List[dict]:
    """The pool of workflow inputs, made on the device in one jitted call.

    Each input holds ``frames`` float32 frames of standard normal noise.
    Frame contrasts are spaced evenly in log over the mix's ``contrast``
    range and dealt out in an order drawn from the seed, so every seed
    has the same set of frame sharpnesses, well apart, and ingest keeps
    the same share of frames whatever the seed."""
    import jax
    import jax.numpy as jnp
    from perfbench.common import prng_key
    if mix.get("kind") != "workflow":
        raise ValueError(f"not a workflow mix: {mix.get('kind')!r}")
    n, (h, w) = config["frames"], config["frame_hw"]
    scales = jnp.asarray(np.geomspace(*mix["contrast"], n), jnp.float32)

    @jax.jit
    def make(key):
        pool = []
        for k in jax.random.split(key, mix["payloads"]):
            k_noise, k_order = jax.random.split(k)
            s = jax.random.permutation(k_order, scales)
            pool.append(jax.random.normal(k_noise, (n, h, w), jnp.float32)
                        * s[:, None, None])
        return pool

    return [{"frames": f} for f in make(prng_key(seed, _PAYLOAD))]


class Sampler:
    """Which of a run's requests or instances are checked: each with
    probability ``1 / every``, at most ``cap`` of them, from the seed."""

    def __init__(self, seed: int, every: int, cap: int):
        self.rng = np.random.default_rng([seed, _SAMPLE])
        self.every, self.cap, self.taken = every, cap, 0

    def __call__(self) -> bool:
        hit = self.rng.integers(self.every) == 0 and self.taken < self.cap
        self.taken += int(hit)
        return bool(hit)


def seeded_order(seed: int, n: int) -> np.ndarray:
    """A permutation of ``range(n)`` drawn from the seed."""
    return np.random.default_rng([seed, _SAMPLE]).permutation(n)
