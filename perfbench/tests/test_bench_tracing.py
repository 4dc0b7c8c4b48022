"""The trace reduction, on a hand-made trace and on a recorded one."""
from pathlib import Path

import jax
import pytest

from perfbench import tracing

DATA = Path(__file__).resolve().parent / "data"
US = 1000  # ns


def _hand_made():
    """Window 0-100 us; device ops 10-30, 20-40 (overlapping), 60-65,
    80-80.5 and 85-88; the host is in `step` 0-100, in `emit` 42-58 and
    in `prepare` 66-79."""
    host = [(tracing.WINDOW, 0, 100 * US), ("step", 0, 100 * US),
            ("emit", 42 * US, 58 * US), ("prepare", 66 * US, 79 * US)]
    ops = [("fusion.1", 10 * US, 30 * US), ("fusion.2", 20 * US, 40 * US),
           ("copy.3", 60 * US, 65 * US), ("copy.3", 80 * US, 80 * US + 500),
           ("copy.4", 85 * US, 88 * US)]
    planes = {"/host:CPU": {"python": host},
              "/device:TPU:0": {"XLA Ops": ops, "XLA Modules":
                                [("jit_step", 10 * US, 71 * US)]}}
    return jax.profiler.ProfileData.from_text_proto(
        tracing.to_text_proto(planes))


def test_reduce_hand_made_trace():
    red = tracing.reduce(_hand_made())
    assert red["window_s"] == pytest.approx(100e-6)
    # union: 10-40, 60-65, 80-80.5, 85-88 -> 38.5 us busy
    assert red["busy_s"] == pytest.approx(38.5e-6)
    assert red["idle_pct"] == pytest.approx(61.5)
    ops = dict(red["device_ops"])
    assert ops["fusion.1"] == pytest.approx(20e-6)
    assert ops["copy.3"] == pytest.approx(5.5e-6)
    gaps = dict(red["idle_gaps"])
    # 0-10 and 88-100 under `step`; 40-60 (midpoint 50) in `emit`;
    # 65-80 (midpoint 72.5) in `prepare`; 80.5-85 is under 10 us
    assert gaps["step"] == pytest.approx(22e-6)
    assert gaps["emit"] == pytest.approx(20e-6)
    assert gaps["prepare"] == pytest.approx(15e-6)
    assert gaps[tracing.SHORT_GAPS] == pytest.approx(4.5e-6)
    assert sum(gaps.values()) == pytest.approx(61.5e-6)


def test_reduce_needs_a_window_and_a_device():
    no_window = {"/host:CPU": {"python": [("step", 0, 10)]},
                 "/device:TPU:0": {"XLA Ops": [("f", 0, 5)]}}
    with pytest.raises(RuntimeError):
        tracing.reduce(jax.profiler.ProfileData.from_text_proto(
            tracing.to_text_proto(no_window)))
    no_device = {"/host:CPU": {"python": [(tracing.WINDOW, 0, 10)]}}
    with pytest.raises(RuntimeError):
        tracing.reduce(jax.profiler.ProfileData.from_text_proto(
            tracing.to_text_proto(no_device)))


def test_reduce_recorded_decode_steps():
    """A few danube decode steps recorded on a TPU v5e, trimmed to the
    device's op line and the host thread that served them."""
    pd = jax.profiler.ProfileData.from_text_proto(
        (DATA / "danube_decode_steps.pbtxt").read_text())
    red = tracing.reduce(pd)
    planes = {p.name: {l.name: [(e.name, e.start_ns, e.end_ns)
                                for e in l.events] for l in p.lines}
              for p in pd.planes}
    host = planes["/host:CPU"]["python3"]
    (lo, hi), = [(s, e) for n, s, e in host if n == tracing.WINDOW]
    # busy time, counted another way: sweep over sorted starts and ends
    ops = [(max(s, lo), min(e, hi)) for _, s, e in
           planes["/device:TPU:0"]["XLA Ops"] if min(e, hi) > max(s, lo)]
    marks = sorted([(s, 1) for s, _ in ops] + [(e, -1) for _, e in ops],
                   key=lambda m: (m[0], -m[1]))
    busy, depth, since = 0.0, 0, None
    for t, d in marks:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    assert red["busy_s"] == pytest.approx(busy * 1e-9)
    assert red["window_s"] == pytest.approx((hi - lo) * 1e-9)
    assert red["idle_pct"] == pytest.approx(19.412851536148256)
    gaps = dict(red["idle_gaps"])
    # the host waits on the sampled token while the device idles
    assert max(gaps, key=gaps.get) == "np.asarray(jax.Array)"
    assert sum(gaps.values()) == pytest.approx(red["window_s"] -
                                               red["busy_s"])
    ops = [name for name, _ in red["device_ops"]]
    assert not any(name.startswith("%while") for name in ops)
    assert ops[0].startswith("%fusion.85 = (bf16[6912], bf16[6912])")
