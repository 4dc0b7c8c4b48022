"""Run one benchmark cell on the chip and print its result line.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration and its traffic mix are looked up by name in
``BENCHMARK.json``; see ``perfbench/common.py`` for where each lives.
The run refuses any device that is not a TPU, and a machine with fewer
chips than the cell asks for: it then exits non-zero and prints no
result.  Set-up (``setup_s``) counts from the start of this process to
the start of the window.  JAX's compile cache is kept inside the
checkout (``.jax_cache``) unless ``JAX_COMPILATION_CACHE_DIR`` names
another directory.

With ``--trace 0`` the result's metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, the device's busy and
window seconds, and a breakdown of device time and idle gaps.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench import common  # noqa: E402


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    return ap.parse_args(argv)


def prepare(bench: dict, cell_name: str):
    """(cell, configuration, mix, runner, reference) found by name."""
    import json
    cell = common.find(bench["workloads"], cell_name, "workload")
    entry = common.find(bench["configs"], cell["config"], "config")
    path = common.CHECKOUT / entry["file"]
    if not path.is_file():
        raise common.BenchError(f"{path} not found")
    config = json.loads(path.read_text())
    mix = common.read_json("traffic", cell["traffic"])
    if not (common.SRC / "repro").is_dir():
        raise common.BenchError(f"the program is not in {common.SRC}")
    if str(common.SRC) not in sys.path:
        sys.path.insert(0, str(common.SRC))
    runner = common.load_module("runners", config["runner"])
    ref = common.load_module("refs", config["reference"])
    return cell, config, mix, runner, ref


def main(argv=None) -> int:
    args = parse(argv)
    try:
        bench = common.load_benchmark()
        cell, config, mix, runner, ref = prepare(bench, args.workload)
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        devs = common.require_tpu(cell["chips"])
        peak = common.peaks(devs[0].device_kind)
        common.note(f"set-up: JAX and the chip ready at "
                    f"{common.clock() - T_PROCESS:.3f} s")
    except common.BenchError as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2

    out = runner.run(config, mix, args.seed, args.seconds, bool(args.trace),
                     devs[:cell["chips"]], T_PROCESS, ref, peak)
    device = out["device"]
    if args.trace:
        names = common.cell_metric_names(bench, cell["name"], "per_layer")
        metrics = common.read_layer_metrics(names, out["run"])
        tr = out["run"].trace
        device.update(busy_s=tr["busy_s"], window_s=tr["window_s"])
        breakdown = {"device_ops": tr["device_ops"],
                     "idle_gaps": tr["idle_gaps"]}
    else:
        names = common.cell_metric_names(bench, cell["name"], "end_to_end")
        metrics = {k: out["e2e"][k] for k in names}
        breakdown = None
    checks = out["checks"]
    common.emit_result(checks.correct, out["attempted"], out["failed"],
                       metrics, device, checks, breakdown)
    return 0


if __name__ == "__main__":
    sys.exit(main())
