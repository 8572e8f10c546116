"""Benchmark harness: one module per paper table/figure + roofline summary.

Prints ``name,value,notes`` CSV rows. Modules:

  approx_error       — paper Fig. 3/4 (Fourier truncation error)
  attention_scaling  — the linear-vs-quadratic memory claim (Sec. II-B)
  agent_sim_table1   — Table I proxy on synthetic scenes (NLL by encoding)
  scenario_eval      — closed-loop per-family eval on the lane-graph
                       scenario suite (minADE/miss/collision/off-road)
  train_bench        — BC trainer throughput (steps/s, datagen cost, loss
                       trajectory) -> BENCH_train.json
  rollout_bench      — cached-decode throughput: ragged decode kernel vs
                       generic full-cache scan, cache-dtype sweep,
                       flat-in-max_len regression -> BENCH_rollout.json
  serve_bench        — continuous-batching SimServer under Poisson
                       arrivals: scenes/s + p50/p99 tick latency per
                       slot count, slab accounting, parity vs batch
                       eval -> BENCH_serve.json
  fleet_bench        — scene-sharded fleet rollouts on a forced
                       multi-device CPU mesh: scenes/s vs device count
                       (bit-parity enforced) + the real-budget Table-I
                       comparison through the dp_compress training path
                       -> BENCH_fleet.json (runs in this process and
                       needs four devices; see its docstring)
  adaptive_basis     — beyond-paper: scale-adaptive basis truncation
  kernel_bench       — kernel micro-times + Pallas/oracle parity
                       (fwd, bwd, and ragged-decode modes)
  roofline_summary   — aggregates experiments/dryrun/*.json if present

Every registered benchmark additionally persists its CSV rows as
``BENCH_<name>.json`` at the repo root (status, elapsed, and the rows it
printed), so successive PRs accumulate a machine-readable perf
trajectory for *all* benchmarks, not just the ones that write their own
rich records (train_bench/rollout_bench keep doing that too).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import traceback


def _report(name, value, extra=""):
    print(f"{name},{value},{extra}", flush=True)


def roofline_summary(report):
    here = os.path.dirname(os.path.abspath(__file__))
    d = os.path.join(here, "..", "experiments", "dryrun")
    if not os.path.isdir(d):
        report("roofline/available", 0, "run repro.launch.dryrun first")
        return
    n_ok = n_err = 0
    for name in sorted(os.listdir(d)):
        if not name.endswith(".json"):
            continue
        with open(os.path.join(d, name)) as f:
            rec = json.load(f)
        if rec.get("status") == "ok":
            n_ok += 1
            t = rec.get("terms")
            if t is None:    # multi-pod cells are compile proofs only
                report(f"roofline/{rec['arch']}/{rec['shape']}/{rec['mesh']}",
                       "compiled",
                       f"hbm_gib={rec.get('hbm_per_chip_gib', 0):.2f}")
                continue
            report(f"roofline/{rec['arch']}/{rec['shape']}/{rec['mesh']}",
                   t["bound_s"],
                   f"dom={t['dominant']} compute_ms={t['compute_s']*1e3:.2f} "
                   f"mem_ms={t['memory_s']*1e3:.2f} "
                   f"coll_ms={t['collective_s']*1e3:.2f}")
        elif rec.get("status") == "error":
            n_err += 1
    report("roofline/cells_ok", n_ok)
    report("roofline/cells_error", n_err)


def _persist(name: str, rows, elapsed_s: float, status: str,
             error: str = "") -> str:
    """Write one benchmark's CSV rows to BENCH_<name>.json (repo root)."""
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "..", f"BENCH_{name}.json")
    rec = {"benchmark": name, "status": status,
           "elapsed_s": round(elapsed_s, 2), "rows": rows}
    if error:
        rec["error"] = error
    with open(path, "w") as f:
        json.dump(rec, f, indent=2)
    return os.path.abspath(path)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of benchmarks")
    ap.add_argument("--table1-steps", type=int, default=150)
    ap.add_argument("--scenario-train-steps", type=int, default=100)
    ap.add_argument("--train-bench-steps", type=int, default=80)
    ap.add_argument("--rollout-smoke", action="store_true",
                    help="run rollout_bench at CI (smoke) size")
    ap.add_argument("--serve-smoke", action="store_true",
                    help="run serve_bench at CI (smoke) size")
    ap.add_argument("--fleet-smoke", action="store_true",
                    help="run fleet_bench at CI (smoke) size")
    args = ap.parse_args()

    from benchmarks import (adaptive_basis, agent_sim_table1, approx_error,
                            attention_scaling, fleet_bench, kernel_bench,
                            rollout_bench, scenario_eval, serve_bench,
                            train_bench)

    def run_rollout(report):
        if args.rollout_smoke:
            # smoke numbers go to /tmp so they never clobber the
            # committed full-size BENCH_rollout.json record
            return rollout_bench.run(report, num_agents=8, num_steps=32,
                                     num_map=8, n_scenes=2, n_samples=2,
                                     overalloc=4, reps=3, min_speedup=1.2,
                                     max_flat_dev=0.5, smoke=True,
                                     out="/tmp/BENCH_rollout_smoke.json")
        return rollout_bench.run(report, reps=2, min_speedup=2.0,
                                 max_flat_dev=0.2)

    def run_serve(report):
        if args.serve_smoke:
            # smoke numbers go to /tmp so they never clobber the
            # committed full-size BENCH_serve.json record
            return serve_bench.run(report, slot_counts=(2, 4), n_scenes=8,
                                   num_map=8, num_agents=4, num_steps=12,
                                   rate=1.0, smoke=True,
                                   out="/tmp/BENCH_serve_smoke.json")
        return serve_bench.run(report)

    def run_fleet(report):
        # In this process: a child process could not open a chip this
        # process already holds. The 1/2/4 curve needs four devices; on
        # CPU, start the process with XLA_FLAGS=
        # --xla_force_host_platform_device_count=4 (fleet_bench refuses
        # fewer).
        return fleet_bench.run(
            report, smoke=args.fleet_smoke,
            out=(os.path.join(tempfile.gettempdir(),
                              "BENCH_fleet_smoke.json")
                 if args.fleet_smoke else fleet_bench.DEF_OUT))

    benches = {
        "approx_error": lambda r: approx_error.run(r),
        "attention_scaling": lambda r: attention_scaling.run(r),
        "adaptive_basis": lambda r: adaptive_basis.run(r),
        "kernel_bench": lambda r: kernel_bench.run(r),
        "agent_sim_table1": lambda r: agent_sim_table1.run(
            r, steps=args.table1_steps),
        "scenario_eval": lambda r: scenario_eval.run(
            r, train_steps=args.scenario_train_steps),
        "train_bench": lambda r: train_bench.run(
            r, steps=args.train_bench_steps),
        "rollout_bench": run_rollout,
        "serve_bench": run_serve,
        "fleet_bench": run_fleet,
        "roofline_summary": lambda r: roofline_summary(r),
    }
    only = set(args.only.split(",")) if args.only else set(benches)
    failures = 0
    for name, fn in benches.items():
        if name not in only:
            continue
        rows = []

        def report(n, value, extra=""):
            _report(n, value, extra)
            rows.append({"name": str(n), "value": str(value),
                         "notes": str(extra)})

        t0 = time.time()
        try:
            fn(report)
            elapsed = time.time() - t0
            _report(f"{name}/elapsed_s", f"{elapsed:.1f}")
            _persist(name, rows, elapsed, "ok")
        except Exception as e:
            failures += 1
            _report(f"{name}/FAILED", type(e).__name__, str(e)[:200])
            traceback.print_exc(file=sys.stderr)
            _persist(name, rows, time.time() - t0, "failed",
                     f"{type(e).__name__}: {e}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
