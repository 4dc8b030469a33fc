"""Readings for the limits of ``correct``: many seeds of one cell in one
process, the program as configured or with a control or fault of
``plants.py`` planted under the timed path.

    python3 chipbench/readings.py --workload <cell> --seeds 1,2,3 [--plant bf16]

Every seed goes through ``run.run_cell`` itself (set-up, the first calls, a
short window, the comparison with the reference); one line a seed goes to
standard output and, with ``--out``, to a file.  Runs on the chip only.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--plant", default=None)
    ap.add_argument("--seconds", type=float, default=0.3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import run
    from r2d2dpg_tpu.utils.startup import enable_compile_cache, require_tpu

    enable_compile_cache()
    device = require_tpu()
    sink = open(args.out, "a") if args.out else None
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.time()
            try:
                r = run.run_cell(args.workload, seed, args.seconds, False,
                                 plant=args.plant, t_start=t0, device=device)
                line = {"seed": seed, "plant": args.plant, "correct": r["correct"],
                        "seconds": time.time() - t0,
                        "metrics": {k: v["value"] for k, v in r["metrics"].items()},
                        "compared": {k: v["value"] for k, v in r["compared"].items()}}
            except Exception as e:  # noqa: BLE001 — a control that crashes has failed
                line = {"seed": seed, "plant": args.plant, "correct": False,
                        "error": f"{type(e).__name__}: {str(e)[:300]}"}
            text = json.dumps(line)
            print(text, flush=True)
            if sink:
                sink.write(text + "\n")
                sink.flush()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
