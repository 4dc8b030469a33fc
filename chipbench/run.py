"""The benchmark's one command.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process: compile cache on, a look for the chip, the cell's files found by
name, set-up (weights and replay from the seed, the first calls, warm-up),
the measured window, with ``--trace 1`` a short profiled stretch of the same
steady loop and the probes, then the comparison with the plain reference.
The last line of standard output is the contract's JSON object.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.time()  # set-up is counted from here: imports included

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def run_cell(workload, seed, seconds, trace, *, plant=None, t_start=None,
             device=None, root=None, bench=None):
    """Everything of a run after the look for the chip; returns the result
    object.  Tests call this on the CPU with a tiny configuration."""
    from chipbench import counts, harness, trace as trace_mod

    t_start = T_START if t_start is None else t_start
    root = harness.HERE if root is None else root
    bench = harness.load_benchmark() if bench is None else bench
    cell = harness.load_json("workloads", workload, root)
    config = harness.load_json("configs", cell["config"], root)
    driver = harness.load_module("drivers", cell["driver"], root)
    device = dict(device or harness.device_record())
    ctx = harness.Context(
        cell_name=workload, cell=cell, config=config, seed=int(seed),
        seconds=float(seconds), trace=bool(trace), plant=plant,
        t_start=t_start, device=device,
    )
    if trace:
        ctx.peaks = counts.load_peaks(device["kind"])
    compiles = harness.CompileCounter()
    ctx.log(f"device {device}")

    session = driver.setup(ctx)
    setup_s = time.time() - t_start
    ctx.log(f"set-up done in {setup_s:.2f} s; measuring {seconds} s")

    before = compiles.count
    ctx.window = driver.window(session, float(seconds))
    compiled_in_window = compiles.count - before
    device["memory_peak_bytes"] = harness.memory_peak_bytes()
    ctx.log(f"window: {json.dumps(ctx.window)}")
    hbm = (ctx.peaks or {}).get("hbm_bytes")
    ctx.log(f"memory_peak_bytes {device['memory_peak_bytes']}"
            + (f" = {100.0 * device['memory_peak_bytes'] / hbm:.1f} % of the chip"
               if hbm else ""))

    breakdown = None
    if trace:
        trace_seconds = float(cell.get("trace_seconds", 1.0))
        with harness.profile_session() as cap:
            steady = driver.window(session, trace_seconds)
        ctx.steady_trace = trace_mod.reduce_file(cap["xplane"], steady["elapsed_s"])
        cap["cleanup"]()
        if hasattr(driver, "probes"):
            with harness.profile_session() as cap:
                ctx.probes = driver.probes(session)
            ctx.probe_trace = trace_mod.reduce_file(cap["xplane"], cap["seconds"])
            cap["cleanup"]()
        device["busy_s"] = ctx.steady_trace["busy_s"]
        device["window_s"] = ctx.steady_trace["window_s"]
        breakdown = {
            "device_ops": ctx.steady_trace["top_ops"],
            "idle_gaps": ctx.steady_trace["top_gaps"],
        }

    ctx.log("comparing with the reference")
    compared = driver.check(ctx, session)
    ctx.log("compared")
    compared.append(harness.Compared("compiles_in_window", compiled_in_window, 0))

    metrics = {}
    if trace:
        for m in harness.metrics_of(bench, "per_layer", workload):
            spec = harness.load_json("metrics", m["name"], root)
            reducer = harness.load_module("reducers", spec["reducer"], root)
            value = reducer.read(ctx, **spec.get("args", {}))
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    else:
        values = dict(ctx.window["metrics"], setup_s=setup_s)
        for m in harness.metrics_of(bench, "end_to_end", workload):
            metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    result = {
        "correct": all(c.ok for c in compared),
        "attempted": int(ctx.window["attempted"]),
        "failed": int(ctx.window["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = harness.compared_dict(compared)
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--plant", default=None,
                    help="a control or fault of chipbench/plants.py (never "
                         "set by the benchmark's own runs)")
    args = ap.parse_args(argv)

    from chipbench import harness
    from r2d2dpg_tpu.utils.startup import enable_compile_cache, require_tpu

    cell = harness.load_json("workloads", args.workload)
    harness.load_json("configs", cell["config"])
    enable_compile_cache()
    device = require_tpu()  # exits non-zero, naming the platform, off the chip
    chips = next(w["chips"] for w in harness.load_benchmark()["workloads"]
                 if w["name"] == args.workload)
    if device["count"] < chips:
        raise SystemExit(
            f"{args.workload} needs {chips} chips; JAX found {device['count']}")

    result = run_cell(args.workload, args.seed, args.seconds, args.trace,
                      plant=args.plant, device=device)
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']!r} (limit {c['limit']!r})"
              f"{'' if c['ok'] else '  <-- FAILS'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
