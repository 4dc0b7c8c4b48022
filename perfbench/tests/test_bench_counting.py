"""Traffic, work counting, peaks and percentiles of the benchmark."""
import json
import random

import numpy as np
import pytest

from perfbench import common, traffic, work

DANUBE = json.loads((common.BENCH_DIR / "configs" /
                     "h2o-danube-1.8b.json").read_text())
MIX = json.loads((common.BENCH_DIR / "traffic" /
                  "azure-conv.json").read_text())


def _take(it, n):
    return [next(it) for _ in range(n)]


def test_serving_traffic_same_lengths_other_ids():
    n = 2 * len(MIX["requests"]) + 3
    a = _take(traffic.serving_requests(MIX, 32000, 11), n)
    b = _take(traffic.serving_requests(MIX, 32000, 2**33 + 7), n)
    assert [(len(p), k) for p, k in a] == [(len(p), k) for p, k in b]
    assert [(len(p), k) for p, k in a[:len(MIX["requests"])]] == \
        [tuple(r) for r in MIX["requests"]]
    assert any(not np.array_equal(p, q) for (p, _), (q, _) in zip(a, b))
    ids = np.concatenate([p for p, _ in a])
    assert ids.min() >= 2 and ids.max() < 32000
    again = _take(traffic.serving_requests(MIX, 32000, 11), n)
    assert all(np.array_equal(p, q) for (p, _), (q, _) in zip(a, again))


def test_workflow_payloads_follow_seed_and_shapes():
    cfg = {"frames": 16, "frame_hw": [64, 64]}
    mix = {"kind": "workflow", "payloads": 3, "contrast": [0.5, 2.0]}
    a = traffic.workflow_payloads(mix, cfg, 5)
    b = traffic.workflow_payloads(mix, cfg, 5)
    c = traffic.workflow_payloads(mix, cfg, 2**33 + 6)
    assert len(a) == 3 and a[0]["frames"].shape == (16, 64, 64)
    assert a[0]["frames"].dtype == np.float32
    assert np.array_equal(a[2]["frames"], b[2]["frames"])
    assert not np.array_equal(a[0]["frames"], c[0]["frames"])

    # every seed deals out the same contrasts, in an order of its own
    def contrasts(pool):
        return np.std(np.asarray(pool[0]["frames"]), axis=(1, 2))
    want = np.geomspace(0.5, 2.0, 16)
    assert np.allclose(np.sort(contrasts(a)), want, rtol=0.05)
    assert np.allclose(np.sort(contrasts(c)), want, rtol=0.05)
    assert not np.array_equal(np.argsort(contrasts(a)),
                              np.argsort(contrasts(c)))
    with pytest.raises(ValueError):
        traffic.workflow_payloads(MIX, cfg, 5)


def test_sampler_is_seeded_and_capped():
    def draws(seed):
        pick = traffic.Sampler(seed, 4, 5)
        return [pick() for _ in range(200)]
    assert draws(9) == draws(9) != draws(10)
    assert sum(draws(9)) == 5


# hand counts for h2o-danube-1.8b: d 2560, 32 heads over 8 of 80,
# d_ff 6912, vocab 32000, 24 layers, window 4096
LAYER = 2560 * 2560 + 2 * 2560 * 640 + 2560 * 2560 + 3 * 2560 * 6912
HEAD = 32000 * 2560
WEIGHT_BYTES = (24 * LAYER + HEAD) * 2 + (2 * 24 + 1) * 2560 * 4
KV = 2 * 8 * 80 * 2                    # K and V of one position, bf16


def test_danube_decode_token_counts():
    assert LAYER == 69_468_160
    got = work.decode_token(DANUBE, 1000)
    assert got["flops"] == 2 * (24 * LAYER + HEAD) + 4 * 32 * 80 * 1000 * 24
    assert got["bytes"] == WEIGHT_BYTES + 2560 * 2 + KV * 1000 * 24 + KV * 24
    # past the window only the window is read
    far = work.decode_token(DANUBE, 6000)
    assert far["bytes"] == WEIGHT_BYTES + 2560 * 2 + KV * 4096 * 24 + KV * 24


def test_danube_prompt_counts():
    got = work.prompt(DANUBE, 2048)
    causal = 2048 * 2049 // 2
    assert got["flops"] == (2 * 2048 * 24 * LAYER + 2 * HEAD
                            + 4 * 32 * 80 * causal * 24)
    assert got["bytes"] == WEIGHT_BYTES + 2048 * 2560 * 2 + 2048 * 24 * KV
    long = work.prompt(DANUBE, 5000)
    W = 4096
    assert long["flops"] - 2 * 5000 * 24 * LAYER - 2 * HEAD == \
        4 * 32 * 80 * 24 * (W * (W + 1) // 2 + (5000 - W) * W)


def test_least_seconds_and_peaks():
    v5e = common.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["bytes_per_s"] == 819e9
    tok = work.decode_token(DANUBE, 512)
    assert work.least_seconds(tok, v5e) == tok["bytes"] / 819e9
    big = work.prompt(DANUBE, 2048)
    assert work.least_seconds(big, v5e) == big["flops"] / 197e12
    with pytest.raises(common.BenchError):
        common.peaks("TPU v9 imaginary")


def test_percentile_is_the_programs_arithmetic():
    from repro.sim.metrics import percentile
    rng = random.Random(3)
    for n in (0, 1, 2, 7, 100, 2000):
        xs = [rng.expovariate(1.0) for _ in range(n)]
        for p in (0, 50, 95, 99, 100):
            assert common.percentile(xs, p) == percentile(xs, p)
