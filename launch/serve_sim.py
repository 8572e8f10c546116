"""Launch a continuous-batching simulation service under Poisson traffic.

Stands up a :class:`repro.runtime.SimServer`, streams procedurally
generated scenes at it with exponential inter-arrival gaps (the
open-loop traffic model serving systems are sized against), and reports
sustained scenes/s, tick latency percentiles, and slab-cache accounting.

Run:  PYTHONPATH=src python launch/serve_sim.py --slots 8 --scenes 32
      PYTHONPATH=src python launch/serve_sim.py --cache-dtype int8 --rate 0.5

See ``docs/serving.md`` for the slot lifecycle and isolation argument,
``benchmarks/serve_bench.py`` for the registered benchmark variant.
"""
import argparse
import logging
import time

import jax

from repro import obs
from repro.launch.compile_cache import enable_compile_cache
from repro.nn import module as nnm
from repro.nn.agent_sim import AgentSimConfig, AgentSimModel
from repro.runtime.sim_server import SceneRequest, SimServer, poisson_drive
from repro.scenarios import ScenarioConfig
from repro.scenarios.registry import generate_mixed


def build(args):
    scen = ScenarioConfig(num_map=args.num_map, num_agents=args.num_agents,
                          num_steps=args.num_steps)
    head_dim = args.d_model // args.heads
    if args.encoding == "se2_fourier":
        head_dim = -(-head_dim // 6) * 6      # encoding needs 6 | head_dim
    cfg = AgentSimConfig(d_model=args.d_model, num_layers=args.layers,
                         num_heads=args.heads, head_dim=head_dim,
                         d_ff=4 * args.d_model,
                         num_actions=scen.num_actions,
                         encoding=args.encoding)
    model = AgentSimModel(cfg)
    params = nnm.init_params(model.specs(), jax.random.key(args.seed))
    return scen, model, params


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--scenes", type=int, default=32)
    ap.add_argument("--rate", type=float, default=1.0,
                    help="mean Poisson arrivals per service tick")
    ap.add_argument("--t-hist", type=int, default=4)
    ap.add_argument("--num-map", type=int, default=32)
    ap.add_argument("--num-agents", type=int, default=8)
    ap.add_argument("--num-steps", type=int, default=16)
    ap.add_argument("--d-model", type=int, default=64)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--encoding", default="se2_fourier")
    ap.add_argument("--cache-dtype", default=None,
                    help="float32 / bfloat16 / int8 (default: model dtype)")
    ap.add_argument("--decode-impl", default=None,
                    help="auto / flash_decode / xla / ref (default: model)")
    ap.add_argument("--drain-lag", type=int, default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry-out", default=None, metavar="PATH",
                    help="write the Chrome/Perfetto trace (spans + final "
                         "registry snapshot) to PATH after the drive; "
                         "render it with python -m repro.launch.obs_report")
    ap.add_argument("--prom-out", default=None, metavar="PATH",
                    help="also dump the registry in Prometheus text "
                         "exposition format")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the drive into "
                         "DIR (TensorBoard/Perfetto-loadable; the "
                         "sim_server named_scopes label the XLA ops)")
    ap.add_argument("--postmortem-out", default=None, metavar="PATH",
                    help="dump a SimServer flight-recorder bundle (per-"
                         "slot phase/cursor table + registry tail) to "
                         "PATH after the drive; render with "
                         "python -m repro.launch.obs_report --postmortem")
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    log = logging.getLogger("serve_sim")
    log.info("compilation cache: %s", enable_compile_cache())

    reg = obs.Registry()
    scen, model, params = build(args)
    srv = SimServer(model, params, scen, num_slots=args.slots,
                    cache_dtype=args.cache_dtype,
                    decode_impl=args.decode_impl, drain_lag=args.drain_lag,
                    registry=reg)
    scenes = generate_mixed(args.seed, 0, args.scenes, scen)
    reqs = [SceneRequest(uid=i, tensors=s, t_hist=args.t_hist,
                         seed=args.seed, scene_id=i)
            for i, s in enumerate(scenes)]

    log.info("serving %d scenes over %d slots (slab %d rows/slot, "
             "cache_dtype=%s, decode=%s, rate=%.2f/tick)",
             len(reqs), args.slots, srv.max_len,
             args.cache_dtype or "model", args.decode_impl or "model",
             args.rate)
    if args.profile_dir:
        jax.profiler.start_trace(args.profile_dir)
    t0 = time.perf_counter()
    out = poisson_drive(srv, reqs, rate=args.rate, seed=args.seed,
                        warmup_ticks=1)
    wall = time.perf_counter() - t0
    if args.profile_dir:
        jax.profiler.stop_trace()
        log.info("jax profiler trace written under %s", args.profile_dir)
    hist = out["latency"]                 # post-compile working ticks
    stats = srv.stats()
    assert len(srv.done) == len(reqs), "requests lost"
    log.info("drained %d/%d scenes in %d ticks, %.2fs wall "
             "(%.1f scenes/s sustained)", len(srv.done), len(reqs),
             srv.ticks, wall, len(reqs) / max(hist.sum, 1e-9))
    log.info("tick latency (post-compile): p50 %.2f ms  p99 %.2f ms",
             1e3 * hist.percentile(50), 1e3 * hist.percentile(99))
    log.info("slab: %.1f MiB for %d x %d rows; peak occupancy is live "
             "rows / slab rows per tick", stats["slab_mib"],
             args.slots, srv.max_len)
    log.info("compilations: tick=%d admit=%d (must both be 1)",
             int(stats["tick_compilations"]),
             int(stats["admit_compilations"]))
    if args.telemetry_out:
        obs.write_chrome_trace(reg, args.telemetry_out)
        log.info("telemetry trace: %s (load in Perfetto, or render with "
                 "python -m repro.launch.obs_report %s)",
                 args.telemetry_out, args.telemetry_out)
    if args.prom_out:
        with open(args.prom_out, "w") as f:
            f.write(obs.prometheus_text(reg))
        log.info("prometheus exposition: %s", args.prom_out)
    if args.postmortem_out:
        log.info("flight-recorder bundle: %s",
                 srv.dump_postmortem(args.postmortem_out, reason="manual"))


if __name__ == "__main__":
    main()
