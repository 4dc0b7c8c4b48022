"""Readings that the limits of ``correct`` are set from, in one process.

    python3 perfbench/calibrate.py --workload <cell> --seeds 11,12,... \\
        [--control-seeds 11,12,13] [--seconds S] [--out FILE]

For each seed, one run of the cell as ``run.py`` makes it (set-up, a
window of ``--seconds``, default the benchmark's ``run_seconds``, and the
check), printed as one JSON line with the program's readings.  For the
control seeds, the same sample is then read with the reference put in the
program's place at the nearest precision below the configuration's:
bfloat16 weights are rounded to int8 and to float8_e4m3 per output
channel (serving), float32 bodies become bfloat16 ones (workflows).

Not a part of any run: it is run on the chip, once per cell, to set or
re-check the configuration's ``limits``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from perfbench import common  # noqa: E402
from perfbench.run import prepare  # noqa: E402


def control(runner_name: str, out: dict, config: dict, ref) -> dict:
    """The control's readings on the sample the run kept."""
    import jax.numpy as jnp
    runner = common.load_module("runners", runner_name)
    got = {}
    if runner_name == "serve":
        params, picked = out["kept"]
        for quant in ("int8", "fp8"):
            worst = {}
            for req in picked:
                r = runner.readings(params, config, req, None, ref, quant)
                worst = {k: max(v, worst.get(k, 0.0)) for k, v in r.items()}
            got[quant] = worst
    else:
        worst = {}
        for rec in out["kept"]:
            r = runner.readings(rec, ref, jnp.bfloat16)
            worst = {k: max(v, worst.get(k, 0.0)) for k, v in r.items()}
        got["bfloat16"] = worst
    return got


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    bench = common.load_benchmark()
    cell, config, mix, runner, ref = prepare(bench, args.workload)
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = common.require_tpu(cell["chips"])
    peak = common.peaks(devs[0].device_kind)
    seconds = args.seconds or bench["run_seconds"]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    sink = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t = common.clock()
            out = runner.run(config, mix, seed, seconds, False,
                             devs[:cell["chips"]], t, ref, peak)
            line = {"workload": args.workload, "seed": seed,
                    "correct": out["checks"].correct,
                    "checks": out["checks"].items,
                    "attempted": out["attempted"],
                    "e2e": {k: v["value"] for k, v in out["e2e"].items()},
                    "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
            if seed in controls:
                line["control"] = control(config["runner"], out, config,
                                          ref)
            line["seconds"] = common.clock() - t
            text = json.dumps(line)
            print(text, flush=True)
            if sink:
                sink.write(text + "\n")
                sink.flush()
            del out
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
