"""Agent-sim BC training launcher: ``python -m repro.launch.train_sim``.

Wires the expert-demonstration pipeline (``repro.training.data``) ->
sharded BC train step (``repro.training.steps``) -> fault-tolerant
:class:`Trainer`, with periodic closed-loop evaluation through
``repro.runtime.evaluation`` riding the trainer's eval hook. The same
code path runs a reduced config end-to-end on this CPU host and the full
sim archs on a fleet (mesh axes span the devices; the data cursor shards
by host).

Modes:

  # single-encoding training with periodic closed-loop eval
  python -m repro.launch.train_sim --arch sim-se2-fourier --reduced \
      --steps 200 --eval-every 100

  # the paper's invariant-vs-absolute comparison table (identical budgets)
  python -m repro.launch.train_sim --compare --reduced --steps 200

``--smoke`` shrinks everything to CI size and asserts the run is healthy:
loss decreased from init and the final checkpoint round-trips bit-exactly.
"""
from __future__ import annotations

import argparse
import json
import logging
import os
import signal
import tempfile

import jax
import numpy as np

from repro import obs
from repro.configs import SIM_ARCH_NAMES, get_sim_arch
from repro.data.pipeline import ShardedIterator
from repro.distributed.sharding import (derive_opt_shardings,
                                        sharding_for_specs, use_mesh_rules)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import (make_fleet_mesh, make_mesh_for,
                               make_production_mesh)
from repro.nn import module as nnm
from repro.nn.agent_sim import AgentSimModel
from repro.runtime.evaluation import EvalConfig, evaluate_scenes
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.scenarios import registry
from repro.training.comparison import (COMPARISON_ENCODINGS, format_table,
                                       run_comparison)
from repro.training.data import holdout_batches, make_batch_fn
from repro.training.steps import (bc_optimizer, loss_summary,
                                  make_sim_eval_step, make_sim_train_step,
                                  open_loop_metrics)

log = logging.getLogger("repro.launch.train_sim")

DEFAULT_CKPT_ROOT = "/tmp/repro_sim_ckpt"


def resolve_ckpt_dir(root, arch, smoke: bool) -> str:
    """Per-(arch, shape) checkpoint dir under the chosen root.

    The subdir is salted with the model/scenario shape so restoring a
    checkpoint from a different encoding or a reduced-vs-full run of the
    same arch can never load a mismatched parameter tree. ``--smoke`` with
    no explicit root uses a fresh temp dir: smoke is a health assertion
    and must not silently resume a finished earlier run (0 steps trained,
    empty history).
    """
    if root is None:
        root = (tempfile.mkdtemp(prefix="repro_sim_smoke_") if smoke
                else DEFAULT_CKPT_ROOT)
    sig = (f"{arch.name}_d{arch.d_model}x{arch.num_layers}"
           f"_m{arch.num_map}a{arch.num_agents}t{arch.num_steps}")
    return os.path.join(root, sig)


def make_eval_cb(model, scen, *, holdout, n_scenes_per_family: int,
                 n_samples: int, seed: int):
    """Periodic evaluation closure for the Trainer's eval hook.

    Scenes, the rollout engine, and the jitted open-loop eval step are all
    built once and reused — only ``engine.params`` is swapped per call, so
    every eval after the first runs without recompilation. With more than
    one device the engine shards its scene lanes over all of them
    (``make_fleet_mesh``), taking the training-sharded parameters as they
    come.
    """
    from repro.runtime.rollout import RolloutEngine

    eval_cfg = EvalConfig(t_hist=max(1, scen.num_steps // 2),
                          n_samples=n_samples, seed=seed + 1)
    scenes = [registry.generate_scene(f, seed + 777, i, scen)
              for f in registry.names()
              for i in range(n_scenes_per_family)]
    eval_fn = jax.jit(make_sim_eval_step(model))
    state = {"engine": None, "last": None, "last_step": None}

    def eval_cb(step, params):
        state["last_step"] = step
        if state["engine"] is None:
            state["engine"] = RolloutEngine(
                model, params, scen,
                num_slots=min(32, len(scenes) * eval_cfg.n_samples),
                mesh=make_fleet_mesh() if jax.device_count() > 1 else None)
        state["engine"].params = params
        closed = evaluate_scenes(state["engine"], scenes, eval_cfg)
        open_m = open_loop_metrics(model, params, holdout, eval_fn=eval_fn)
        state["last"] = {"open_loop": open_m,
                         "closed_loop": closed["overall"]}
        log.info(
            "eval @ step %d: nll %.4f acc %.3f | minADE %.3f miss %.3f "
            "collision %.3f offroad %.3f", step, open_m["nll"],
            open_m["accuracy"], closed["overall"]["min_ade"],
            closed["overall"]["miss_rate"],
            closed["overall"]["collision_rate"],
            closed["overall"]["offroad_rate"])

    return eval_cb, state


def _with_nan_injection(step_fn, at_step: int):
    """Failure drill (``--inject-nan-at``): poison the *reported* loss
    from host call ``at_step`` onward so the NaN guard trips and the
    flight-recorder dump path runs for real. The parameter update itself
    is untouched — this perturbs only the metric the guard reads."""
    calls = {"n": 0}

    def wrapped(params, opt_state, batch):
        new_params, new_opt, metrics = step_fn(params, opt_state, batch)
        if calls["n"] >= at_step:
            metrics = dict(metrics)
            metrics["loss"] = float("nan")
        calls["n"] += 1
        return new_params, new_opt, metrics

    return wrapped


def train_single(args) -> dict:
    arch = get_sim_arch(args.arch)
    if args.reduced:
        arch = arch.reduced()
    if args.smoke:
        arch = arch.reduced(num_map=12, num_agents=4, num_steps=8)
    cfg = arch.agent_sim_config()
    scen = arch.scenario_config()
    model = AgentSimModel(cfg)
    specs = model.specs()
    mesh = (make_production_mesh() if args.production_mesh
            else make_mesh_for())
    ckpt_dir = resolve_ckpt_dir(args.ckpt_dir, arch, args.smoke)

    opt = bc_optimizer(args.lr, args.steps)
    data = ShardedIterator(make_batch_fn(scen), batch_size=args.batch,
                           seed=args.seed,
                           host_rank=jax.process_index(),
                           world=jax.process_count())
    holdout = holdout_batches(scen, args.batch, args.holdout_batches,
                              seed=args.seed)

    with use_mesh_rules(mesh):
        param_sh = sharding_for_specs(specs, mesh)
        params = jax.jit(lambda k: nnm.init_params(specs, k),
                         out_shardings=param_sh)(jax.random.key(args.seed))
        opt_state = jax.jit(opt.init, out_shardings=derive_opt_shardings(
            specs, jax.eval_shape(opt.init, params), mesh))(params)
        step = obs.CostAccounted(jax.jit(make_sim_train_step(model, opt)),
                                 "train.step", labels={"arch": arch.name})
        if args.inject_nan_at is not None:
            step = _with_nan_injection(step, args.inject_nan_at)

        eval_cb, eval_state = make_eval_cb(
            model, scen, holdout=holdout,
            n_scenes_per_family=args.eval_scenes_per_family,
            n_samples=args.eval_samples, seed=args.seed)

        # graceful preemption: SIGTERM triggers checkpoint-and-exit
        stop = {"flag": False}
        signal.signal(signal.SIGTERM, lambda *_: stop.update(flag=True))

        flight = (obs.FlightRecorder(out_path=args.postmortem_out)
                  if args.postmortem_out else None)

        trainer = Trainer(
            step, params, opt_state, data, ckpt_dir,
            TrainerConfig(total_steps=args.steps,
                          ckpt_every=args.ckpt_every,
                          log_every=max(1, args.steps // 20),
                          eval_every=args.eval_every),
            metrics_cb=lambda s, m: log.info(
                "step %d loss %.4f acc %.3f (%.2fs/step)", s, m["loss"],
                m.get("accuracy", float("nan")), m["sec_per_step"]),
            should_stop=lambda: stop["flag"],
            param_shardings=param_sh,
            eval_cb=eval_cb,
            flight=flight)
        trainer.restore_if_available(force=args.force)
        out = trainer.run()
        # final eval, unless the cadence already evaluated THIS step in
        # this process (a restored already-complete run, or a NaN-skipped
        # final step, never fired the in-loop hook)
        if eval_state["last_step"] != trainer.step:
            eval_cb(trainer.step, trainer.params)
        data.close()

    result = {
        "arch": arch.name, "encoding": arch.encoding, "status": out["status"],
        "steps": trainer.step,
        # NaN-guard outcome in the final summary: a run that silently
        # discarded updates must say so next to its loss numbers
        "nan_skipped": out.get("nan_skipped", 0),
        **loss_summary(trainer.history),
        **{f"final_{k2}": v for k2, v in
           (eval_state["last"] or {}).get("open_loop", {}).items()},
    }
    closed = (eval_state["last"] or {}).get("closed_loop", {})
    result.update({f"closed_{m}": closed.get(m, float("nan"))
                   for m in ("min_ade", "miss_rate", "collision_rate",
                             "offroad_rate")})
    log.info("finished: %s", result)

    if args.smoke:
        assert out["status"] == "done", out
        assert np.isfinite(result["loss_last"]), result
        assert result["loss_last"] < result["loss_first"], \
            f"loss did not decrease: {result}"
        # checkpoint round-trip: the final save must restore bit-exactly
        tree, extra = trainer.ckpt.restore(trainer.ckpt.latest_step())
        assert int(extra["step"]) == trainer.step
        for a, b in zip(jax.tree.leaves(tree["params"]),
                        jax.tree.leaves(trainer.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        log.info("smoke OK: loss %.4f -> %.4f, checkpoint round-trip exact",
                 result["loss_first"], result["loss_last"])
    return result


def train_compare(args) -> dict:
    arch = get_sim_arch(args.arch)
    if args.reduced or args.smoke:
        arch = arch.reduced()
    if args.smoke:
        arch = arch.reduced(num_map=12, num_agents=4, num_steps=8)
    encodings = (tuple(args.encodings.split(","))
                 if args.encodings else COMPARISON_ENCODINGS)
    if args.smoke and not args.encodings:
        # the acceptance pair: one relative encoding vs the baseline
        encodings = ("se2_fourier", "absolute")
    report = lambda name, val, extra="": print(f"{name},{val},{extra}",
                                               flush=True)
    rows = run_comparison(
        arch, encodings, steps=args.steps, batch=args.batch, lr=args.lr,
        seed=args.seed, holdout_n=args.holdout_batches,
        n_scenes_per_family=args.eval_scenes_per_family,
        eval_samples=args.eval_samples, report=report)
    print(format_table(rows))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=2)
        log.info("wrote %s", args.out)
    if args.smoke:
        for enc in encodings:
            row = rows[enc]
            assert row["status"] == "done", (enc, row)
            assert np.isfinite(row["open_loop_nll"]), (enc, row)
            assert np.isfinite(row["closed_loop_min_ade"]), (enc, row)
            assert row["loss_last"] < row["loss_first"], (enc, row)
        log.info("compare smoke OK: %s", list(encodings))
    return rows


def main():
    ap = argparse.ArgumentParser(
        description="Behavior-cloning training for the SE(2) agent-sim "
                    "model on scenario-family expert demonstrations.")
    ap.add_argument("--arch", default="sim-se2-fourier",
                    help=f"one of {SIM_ARCH_NAMES}")
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized same-encoding config")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="checkpoint root (a per-arch+shape subdir is "
                         f"appended; default {DEFAULT_CKPT_ROOT}, or a "
                         "fresh temp dir under --smoke)")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--eval-every", type=int, default=0,
                    help="closed-loop eval cadence in steps (0 = final only)")
    ap.add_argument("--eval-scenes-per-family", type=int, default=2)
    ap.add_argument("--eval-samples", type=int, default=2)
    ap.add_argument("--holdout-batches", type=int, default=4)
    ap.add_argument("--compare", action="store_true",
                    help="train every encoding under one budget and print "
                         "the invariant-vs-absolute table")
    ap.add_argument("--encodings", default=None,
                    help="comma-separated subset for --compare")
    ap.add_argument("--out", default=None,
                    help="write --compare results to this JSON path")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="resume even from a checkpoint tagged with a "
                         "halt_reason (e.g. a NaN-halt save); without it "
                         "the trainer refuses to blindly replay the same "
                         "divergence")
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized run with health assertions")
    ap.add_argument("--telemetry-out", default=None, metavar="PATH",
                    help="write the run's Chrome/Perfetto telemetry trace "
                         "(trainer step/eval/checkpoint spans + registry "
                         "snapshot) to PATH; render with "
                         "python -m repro.launch.obs_report")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="also dump the registry in Prometheus text "
                         "exposition format")
    ap.add_argument("--telemetry-dir", default=None, metavar="DIR",
                    help="write this process's trace as DIR/rankNNNNN."
                         "trace.jsonl, stamped with its fleet identity; "
                         "merge a fleet's worth with "
                         "python -m repro.launch.obs_merge DIR")
    ap.add_argument("--postmortem-out", default=None, metavar="PATH",
                    help="arm the flight recorder: on NaN-halt or SIGTERM "
                         "preemption, dump a postmortem bundle to PATH "
                         "(render with obs_report --postmortem)")
    ap.add_argument("--inject-nan-at", type=int, default=None, metavar="N",
                    help="failure drill: report NaN losses from step N "
                         "onward so the NaN guard halts and the flight "
                         "recorder fires (exits nonzero by design)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the whole run "
                         "into DIR")
    args = ap.parse_args()

    logging.basicConfig(level=logging.INFO)
    log.info("compilation cache: %s", enable_compile_cache())
    if args.smoke and args.steps == 200:
        args.steps = 40
    # one fresh registry as the process default: the Trainer, every
    # rollout engine the eval hook builds, and any SimServer all land in
    # the same timeline without threading a parameter through
    reg = obs.Registry()
    obs.set_registry(reg)
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    try:
        if args.compare:
            train_compare(args)
        else:
            train_single(args)
    finally:
        if args.profile_dir:
            jax.profiler.stop_trace()
            log.info("jax profiler trace written under %s", args.profile_dir)
        if args.telemetry_out:
            obs.write_chrome_trace(reg, args.telemetry_out)
            log.info("telemetry trace: %s", args.telemetry_out)
        if args.telemetry_dir:
            obs.fleet.stamp_process_identity(reg)
            log.info("per-rank telemetry trace: %s",
                     obs.fleet.write_rank_trace(reg, args.telemetry_dir,
                                                process_name="train_sim"))
        if args.prom_out:
            with open(args.prom_out, "w") as f:
                f.write(obs.prometheus_text(reg))
            log.info("prometheus exposition: %s", args.prom_out)


if __name__ == "__main__":
    main()
