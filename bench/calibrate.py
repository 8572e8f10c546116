"""Readings of a cell's correctness numbers over many seeds, with the control.

    python bench/calibrate.py --workload <cell> --seeds 11,12,13 \
        [--control-seeds 11,12,13] [--seconds 3]

Runs the cell once per seed in this one process (each run as the benchmark
runs it, at the cell's own sizes, with a short window) and prints one JSON
line per seed with every reading and the harness's verdict on them; on the
control seeds it also runs the control (``check.py``: the reference with
float8-rounded matmul operands in the model's place) and prints its
readings and verdict under ``control``. The limits in ``bench/limits/`` are
set from these readings: above the largest reading of the program, below
the smallest of the control. The benchmark's own runs never run the
control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import run  # noqa: E402


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)

    import jax

    from bench.peaks import peaks

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX platform is {dev.platform!r}", file=sys.stderr)
        return 2
    run.enable_compile_cache()
    control = {int(s) for s in args.control_seeds.split(",") if s}
    for s in (int(x) for x in args.seeds.split(",")):
        cell = run.Cell.from_benchmark(
            args.workload, seed=s, seconds=args.seconds, trace=False,
            device=dev, peak=peaks(dev.device_kind), control=s in control)
        out = run.execute(cell)
        ctl = out["control"]
        print(json.dumps({"workload": args.workload, "seed": s,
                          "readings": out["readings"],
                          "correct": run.correct_of(out["readings"],
                                                    cell.limits),
                          "control": ctl and dict(
                              ctl, correct=run.correct_of(ctl, cell.limits)),
                          "attempted": out["attempted"],
                          "failed": out["failed"],
                          "metrics": out["metrics"],
                          "setup_s": out["setup_s"],
                          "info": out["info"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
