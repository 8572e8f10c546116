"""Fleet rollout benchmark: scenes/s vs device count + real-budget Table I.

Two phases, both over the scene-sharded fleet path
(``RolloutEngine(mesh=...)`` shard_mapping its tick over ``("pod",
"data")`` — see ``docs/distributed.md``):

* **scaling curve** — one mixed-family scene workload rolled out at
  every requested device count (device 1 = the unsharded single-device
  engine). Each count reports scenes/s, and every sharded run's futures
  must be BIT-IDENTICAL to the single-device reference — the curve is
  only meaningful if sharding is free of placement effects. On a forced
  CPU mesh (``--xla_force_host_platform_device_count``) the devices are
  virtual and share the host's physical cores, so the curve measures
  dispatch/partitioning overhead rather than real parallel speedup; the
  record carries ``physical_cpus`` so readers can tell. On a real pod
  the same code measures the actual scaling.

* **Table I at a real budget** (``--table1``, on by default for the full
  run) — the PR 4 invariant-vs-absolute comparison executed through the
  production fleet path: training goes through the shard_mapped
  compressed-DP step (int8 + error-feedback cross-pod psum carrying the
  gradient traffic on the "pod" axis), and the closed-loop scoring runs
  10k+ mixed-family scenes through the scene-sharded engine. Output:
  per-family metric tables per encoding plus the paper's headline
  relative-vs-absolute NLL comparison.

Writes the rich record to ``BENCH_fleet.json`` (repo root) and prints
``name,value,notes`` CSV rows like every other benchmark.

Run:  PYTHONPATH=src python benchmarks/fleet_bench.py [--smoke] [--no-table1]

The script forces its own ``--xla_force_host_platform_device_count``
(before first jax init) when launched as __main__; through
``benchmarks/run.py`` it runs in that process, on the devices it sees.
"""
from __future__ import annotations

import argparse
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))
DEF_OUT = os.path.join(HERE, "..", "BENCH_fleet.json")

TABLE1_ENCODINGS = ("se2_fourier", "absolute")   # the acceptance pair


def _fleet_arch(smoke: bool):
    from repro.configs import get_sim_arch
    arch = get_sim_arch("sim-se2-fourier").reduced()
    if smoke:
        arch = arch.reduced(num_map=12, num_agents=4, num_steps=8)
    return arch


def _mixed_scenes(scen, n: int, seed: int = 7):
    """n mixed-family scenes, families interleaved deterministically."""
    from repro.scenarios import registry
    fams = registry.names()
    return [registry.generate_scene(fams[i % len(fams)], seed,
                                    i // len(fams), scen)
            for i in range(n)]


def _per_family_scenes(scen, per_family: int, seed: int):
    from repro.scenarios import registry
    return [registry.generate_scene(f, seed, i, scen)
            for f in registry.names() for i in range(per_family)]


def scaling_curve(report, *, arch, device_counts, n_scenes, n_samples,
                  slots_per_device, seed=0):
    """scenes/s per device count + bit-parity against the 1-device run."""
    import jax
    import numpy as np

    from repro import obs
    from repro.launch.mesh import make_fleet_mesh
    from repro.nn import module as nnm
    from repro.nn.agent_sim import AgentSimModel
    from repro.runtime.rollout import RolloutEngine

    scen = arch.scenario_config()
    model = AgentSimModel(arch.agent_sim_config())
    params = nnm.init_params(model.specs(), jax.random.key(seed))
    t0 = time.time()
    scenes = [s.tensors for s in _mixed_scenes(scen, n_scenes)]
    report("fleet_bench/scene_gen_s", f"{time.time() - t0:.1f}",
           f"n={n_scenes}")
    t_hist = max(1, scen.num_steps // 2)

    curve, ref = [], None
    for d in device_counts:
        # d=1 is the plain single-device engine — the parity reference;
        # even d >= 2 splits a leading 2-wide "pod" axis off so the
        # cross-pod dimension of the spec is exercised, not just "data"
        mesh = (None if d == 1 else
                make_fleet_mesh(d, pods=2 if d % 2 == 0 else 1))
        reg = obs.Registry()
        eng = RolloutEngine(model, params, scen,
                            num_slots=slots_per_device * d, mesh=mesh,
                            registry=reg)
        t0 = time.time()
        eng.run(scenes[:2], t_hist=t_hist, n_samples=n_samples, seed=seed)
        compile_s = time.time() - t0
        warm_steps = reg.histogram("rollout.step.seconds").count
        t0 = time.time()
        fut = eng.run(scenes, t_hist=t_hist, n_samples=n_samples, seed=seed)
        dt = time.time() - t0
        parity = bool(ref is None or np.array_equal(ref, fut))
        ref = fut if ref is None else ref
        mesh_shape = "1" if mesh is None else "x".join(
            str(mesh.shape[a]) for a in ("pod", "data"))
        step_hist = reg.histogram("rollout.step.seconds")
        row = {"devices": d, "mesh": mesh_shape,
               "num_slots": eng.num_slots,
               "scenes_per_s": n_scenes / dt, "lanes": n_scenes * n_samples,
               "run_s": dt, "compile_s": compile_s,
               # registry-derived: per-tick p50 over both runs (the
               # warm-up run's steps are a small, post-compile minority)
               "step_p50_ms": 1e3 * step_hist.percentile(50),
               "steps_timed": step_hist.count - warm_steps,
               "cache_mib": reg.gauge("rollout.cache_bytes").value / 2 ** 20,
               "bit_identical_to_single_device": parity}
        curve.append(row)
        report(f"fleet_bench/curve/d{d}/scenes_per_s",
               f"{row['scenes_per_s']:.2f}",
               f"mesh={mesh_shape} slots={eng.num_slots} parity={parity}")
        assert parity, (
            f"sharded rollout at {d} devices diverged from the "
            f"single-device reference — placement leaked into results")
    return curve


def fleet_telemetry(report, *, arch, ranks, out_dir, n_scenes, n_samples,
                    slots_per_rank, seed=0, smoke=False):
    """Per-rank trace aggregation demo: one registry per rank, each rank
    rolling out its own scene shard (the per-host split of a data-
    parallel fleet, run sequentially in this one process), merged into a
    single Perfetto timeline by ``repro.obs.fleet.merge_traces``.

    The last rank gets a deliberate per-step slowdown injected (a host
    sleep of 3x rank 0's measured step median — a failure drill, clearly
    not a claim about real hardware) so the whole chain fires on honest
    wall-clock: per-rank medians -> ``StragglerPolicy`` flags the slow
    rank on rank 0's registry -> ``obs_merge`` overlays the flag on the
    straggler's own track in the merged trace.
    """
    import jax

    from repro import obs
    from repro.nn import module as nnm
    from repro.nn.agent_sim import AgentSimModel
    from repro.runtime.monitor import StragglerPolicy
    from repro.runtime.rollout import RolloutEngine

    scen = arch.scenario_config()
    model = AgentSimModel(arch.agent_sim_config())
    params = nnm.init_params(model.specs(), jax.random.key(seed))
    scenes = [s.tensors for s in _mixed_scenes(scen, n_scenes)]
    t_hist = max(1, scen.num_steps // 2)
    pods = 2 if ranks % 2 == 0 and ranks > 1 else 1
    per_pod = ranks // pods

    regs, medians, counts = [], {}, {}
    straggle_s = 0.0
    for r in range(ranks):
        reg = obs.Registry()
        obs.fleet.stamp_identity(reg, rank=r, pod=r // per_pod,
                                 data=r % per_pod, world=ranks)
        eng = RolloutEngine(model, params, scen, num_slots=slots_per_rank,
                            registry=reg)
        if r == ranks - 1 and ranks > 1 and straggle_s > 0:
            inner = eng._step

            def slow_step(*a, _inner=inner, _s=straggle_s):
                time.sleep(_s)
                return _inner(*a)

            eng._step = slow_step
        shard = scenes[r::ranks]
        eng.run(shard, t_hist=t_hist, n_samples=n_samples, seed=seed)
        h = reg.histogram("rollout.step.seconds")
        medians[r], counts[r] = h.percentile(50), h.count
        regs.append(reg)
        if r == 0:
            straggle_s = 3.0 * max(medians[0], 1e-4)

    policy = StragglerPolicy(straggler_factor=1.5,
                             min_samples=min(10, min(counts.values())),
                             registry=regs[0])
    flagged = policy.evaluate(medians, counts)
    report("fleet_bench/telemetry/flagged",
           ",".join(map(str, flagged)) or "none",
           " ".join(f"r{r}={m * 1e3:.2f}ms" for r, m in medians.items()))

    paths = [obs.fleet.write_rank_trace(reg, out_dir,
                                        process_name="fleet_bench")
             for reg in regs]
    merged = obs.fleet.merge_traces(
        paths, os.path.join(out_dir, "merged.trace.jsonl"))
    report("fleet_bench/telemetry/merged", merged["out"],
           f"ranks={len(merged['ranks'])} events={merged['events']} "
           f"overlays={merged['straggler_overlays']}")
    if smoke:
        assert flagged == [ranks - 1], (
            f"straggler drill: expected rank {ranks - 1} flagged, "
            f"got {flagged} (medians {medians})")
        assert merged["straggler_overlays"] >= 1, merged
    return {"ranks": ranks, "flagged": flagged,
            "step_p50_ms": {str(r): 1e3 * m for r, m in medians.items()},
            "injected_straggle_ms": 1e3 * straggle_s,
            "per_rank_traces": paths, **merged}


def table1(report, *, arch, devices, n_samples, slots_per_device,
           steps, batch, encodings, scenes_per_family, seed=0):
    """The invariant-vs-absolute comparison on the production fleet path."""
    from repro.launch.mesh import make_fleet_mesh
    from repro.training.comparison import format_table, run_comparison

    mesh = make_fleet_mesh(devices, pods=2 if devices % 2 == 0 else 1)
    n_scenes = scenes_per_family * 7   # 7 registered families
    report("fleet_bench/table1/budget",
           f"steps={steps}", f"batch={batch} eval_scenes={n_scenes} "
           f"samples={n_samples} devices={devices}")
    rows = run_comparison(
        arch, encodings, steps=steps, batch=batch, seed=seed,
        n_scenes_per_family=scenes_per_family, eval_samples=n_samples,
        mesh=mesh, dp_compress=True, eval_mesh=mesh,
        eval_num_slots=slots_per_device * devices,
        report=lambda n, v, extra="": report(f"fleet_bench/{n}", v, extra))
    for enc in encodings:
        for fam, m in sorted(rows[enc]["families"].items()):
            report(f"fleet_bench/table1/{enc}/{fam}/min_ade",
                   f"{m['min_ade']:.4f}",
                   f"miss={m['miss_rate']:.4f} "
                   f"collision={m['collision_rate']:.4f} "
                   f"offroad={m['offroad_rate']:.4f} "
                   f"scenes={m['n_scenes']:.0f} agents={m['n_agents']:.0f}")
    print(format_table(rows))
    return rows


def run(report, *, smoke=False, devices=4, device_counts=(1, 2, 4),
        n_scenes=256, n_samples=2, slots_per_device=64, with_table1=True,
        steps=250, batch=32, encodings=TABLE1_ENCODINGS,
        scenes_per_family=1432, seed=0, out=DEF_OUT, telemetry_dir=None):
    import jax
    import numpy as np

    if smoke:
        # trim the curve to the forced device count before validating it,
        # so e.g. --smoke --devices 2 runs the 1,2 prefix instead of
        # demanding the default 4-point curve
        device_counts = tuple(d for d in device_counts if d <= devices)
        n_scenes, slots_per_device = 16, 4
        steps, batch, scenes_per_family = 6, 8, 2
    if len(jax.devices()) < max(device_counts):
        raise RuntimeError(
            f"{len(jax.devices())} devices visible but the curve needs "
            f"{max(device_counts)}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=... before jax init "
            f"(the __main__ entry point does this)")
    arch = _fleet_arch(smoke)
    record = {
        "benchmark": "fleet_bench", "smoke": smoke,
        "arch": {"encoding_curve": arch.encoding, "d_model": arch.d_model,
                 "num_layers": arch.num_layers, "num_map": arch.num_map,
                 "num_agents": arch.num_agents, "num_steps": arch.num_steps},
        "backend": jax.default_backend(),
        "forced_devices": len(jax.devices()),
        "physical_cpus": os.cpu_count(),
    }

    t0 = time.time()
    record["curve"] = scaling_curve(
        report, arch=arch, device_counts=device_counts, n_scenes=n_scenes,
        n_samples=n_samples, slots_per_device=slots_per_device, seed=seed)
    record["curve_elapsed_s"] = round(time.time() - t0, 1)

    if telemetry_dir:
        t0 = time.time()
        record["fleet_telemetry"] = fleet_telemetry(
            report, arch=arch, ranks=devices, out_dir=telemetry_dir,
            n_scenes=min(n_scenes, 8 if smoke else 32),
            n_samples=n_samples, slots_per_rank=min(slots_per_device, 8),
            seed=seed, smoke=smoke)
        record["fleet_telemetry"]["elapsed_s"] = round(time.time() - t0, 1)

    if with_table1:
        t0 = time.time()
        rows = table1(report, arch=arch, devices=devices,
                      n_samples=n_samples, slots_per_device=slots_per_device,
                      steps=steps, batch=batch, encodings=encodings,
                      scenes_per_family=scenes_per_family, seed=seed)
        record["table1"] = {
            "budget": {"steps": steps, "batch": batch,
                       "eval_scenes": scenes_per_family * 7,
                       "eval_samples": n_samples, "devices": devices,
                       "dp_compress": True},
            "rows": rows,
        }
        record["table1_elapsed_s"] = round(time.time() - t0, 1)
        if smoke:
            for enc in encodings:
                r = rows[enc]
                assert r["status"] == "done", (enc, r)
                assert np.isfinite(r["open_loop_nll"]), (enc, r)
                assert np.isfinite(r["closed_loop_min_ade"]), (enc, r)
                assert len(r["families"]) == 8, (enc, list(r["families"]))

    with open(out, "w") as f:
        json.dump(record, f, indent=2)
    report("fleet_bench/out", os.path.abspath(out))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run with structural assertions")
    ap.add_argument("--devices", type=int, default=4,
                    help="forced CPU device count (and the fleet size for "
                         "the Table-I phase)")
    ap.add_argument("--device-counts", default=None,
                    help="comma list for the scaling curve (default 1,2,4)")
    ap.add_argument("--scenes", type=int, default=256)
    ap.add_argument("--samples", type=int, default=2)
    ap.add_argument("--slots-per-device", type=int, default=64)
    ap.add_argument("--no-table1", action="store_true")
    ap.add_argument("--steps", type=int, default=250)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--encodings", default=",".join(TABLE1_ENCODINGS))
    ap.add_argument("--scenes-per-family", type=int, default=1432,
                    help="closed-loop eval scenes per family for Table I "
                         "(1432 x 7 families = 10024 scenes)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None)
    ap.add_argument("--telemetry-dir", default=None, metavar="DIR",
                    help="also run the per-rank telemetry demo: one "
                         "registry per rank, rank*.trace.jsonl files + a "
                         "merged Perfetto timeline (with the straggler "
                         "drill flagged + overlaid) under DIR")
    args = ap.parse_args()

    # MUST precede first jax init: jax locks the device count.
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={args.devices}")

    counts = (tuple(int(x) for x in args.device_counts.split(","))
              if args.device_counts else (1, 2, 4))
    out = args.out or ("/tmp/BENCH_fleet_smoke.json" if args.smoke
                       else DEF_OUT)
    report = lambda name, val, extra="": print(f"{name},{val},{extra}",
                                               flush=True)
    run(report, smoke=args.smoke, devices=args.devices, device_counts=counts,
        n_scenes=args.scenes, n_samples=args.samples,
        slots_per_device=args.slots_per_device,
        with_table1=not args.no_table1, steps=args.steps, batch=args.batch,
        encodings=tuple(args.encodings.split(",")),
        scenes_per_family=args.scenes_per_family, seed=args.seed, out=out,
        telemetry_dir=args.telemetry_dir)


if __name__ == "__main__":
    main()
