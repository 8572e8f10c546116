"""Run one benchmark cell once on the accelerator.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json``'s ``workloads``: it names a
configuration (the file its ``configs`` entry gives, under
``bench/configs/``) and a traffic mix (``bench/traffic/<traffic>.json``),
and its correctness limits are in ``bench/limits/<cell>.json``. Set-up
(weights, inputs, compilation and warm-up) is timed apart from the
measured window.

The traffic's ``kind`` names its runner, the module ``bench/<kind>.py``
(``serve``: ``SimServer`` on the agent-sim configurations). A runner is
the only part that knows the program, so a configuration the runners here
cannot drive joins with new files alone: its runner, configuration,
traffic, limits and readers.

The runner contract: ``run(cell)`` drives the program for
``cell.window_seconds()`` inside ``with cell.profile() as prof``
(``prof.mark_start()`` and ``prof.mark_end()`` around the window), then
checks what the window produced, and returns a dict with

* ``setup_s``: seconds of set-up before the window;
* ``metrics``: ``{name: value}``, every end-to-end metric that lists the
  cell (``setup_s`` aside);
* ``layer``: work counted from shapes in the traced window, for the
  per-layer readers (``{}`` untraced);
* ``readings``: ``{name: value}`` of the correctness numbers, each held to
  ``bench/limits/<cell>.json``, and ``tokens_compared`` (> 0);
* ``control``: the control's readings in the same form, or None;
* ``attempted``, ``failed``: requests due in the window, and those of them
  that did not finish sound;
* ``memory_peak_bytes``: ``cell.memory_peak()``, read before the
  reference runs;
* ``info``: anything else, printed to standard error;
* ``chips`` (optional, default 1): the devices the run used.

A runner may also define ``SPANS``, a tuple of host span-name prefixes
(``serve``: ``("sim_server.",)``): a traced run keeps those spans beside
the benchmark's own ``bench.*`` ones (``bench/trace.py``).

With ``--trace 0`` the result's metrics are the cell's end-to-end metrics;
with ``--trace 1`` a profiler trace of the window is reduced (device time
by op and by named scope, the kept host spans, idle gaps) and read by the
per-layer readers in ``bench/metrics/<metric>.py``.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` when
traced), and last ``checks``, each number compared with its limit. The run
exits non-zero, printing no result, without a TPU, with fewer chips than
the cell asks for, or on a device kind missing from ``bench/peaks.py``.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import math
import os
import shutil
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

#: the longest stretch of the window that a traced run traces
TRACE_SECONDS = 3.0
#: the program's model settings a configuration file must repeat exactly
MODEL_KEYS = ("d_model", "num_layers", "num_heads", "head_dim", "d_ff",
              "num_actions", "agent_feat_dim", "map_feat_dim", "encoding",
              "fourier_terms", "min_scale", "max_scale", "pos_scale",
              "dtype")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


class Cell:
    """One workload's configuration, traffic and limits, plus the run's
    seed, window, device and profiler."""

    def __init__(self, name, config, traffic, limits, *, seed, seconds,
                 trace, device=None, peak=None, control=False):
        self.name, self.config, self.traffic = name, config, traffic
        self.limits = limits
        # None where the configuration has no such key: a catalog model's
        # sizes sit at the file's top level, and only the agent-sim
        # configurations have an action grid and a cache dtype
        self.model = config.get("model")
        self.grid = config.get("action_grid")
        self.cache_dtype = config.get("cache_dtype")
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.device, self.peak, self.control = device, peak, control
        self.trace_result = None

    @classmethod
    def from_benchmark(cls, workload, **kw):
        bench = load_json("BENCHMARK.json")
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; have "
                             f"{sorted(cells)}")
        w = cells[workload]
        conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
        return cls(workload, load_json(conf["file"]),
                   load_json("bench", "traffic", w["traffic"] + ".json"),
                   load_json("bench", "limits", workload + ".json"), **kw)

    # -- the program, held to the configuration file ---------------------------

    def program_config(self, get_sim_arch):
        """The registered arch's model config; it has to match the file."""
        cfg = get_sim_arch(self.config["arch"]).agent_sim_config()
        diff = {k: (getattr(cfg, k), self.model[k]) for k in MODEL_KEYS
                if getattr(cfg, k) != self.model[k]}
        if diff:
            raise ValueError(f"registered {self.config['arch']} differs "
                             f"from its configuration file: {diff}")
        return cfg

    def check_grid(self, scen):
        """The program's action grid and integrator are the file's."""
        import numpy as np

        from repro.core import kinematics

        g = self.grid
        ok = (scen.accel_bins == g["accel_bins"]
              and scen.yaw_bins == g["yaw_bins"]
              and np.isclose(scen.max_accel, g["max_accel"])
              and np.isclose(scen.max_yaw_rate, g["max_yaw_rate"])
              and np.isclose(kinematics.DT, g["dt"])
              and np.isclose(kinematics.MAX_SPEED, g["max_speed"]))
        if not ok:
            raise ValueError("the program's action grid or integrator "
                             f"differs from the configuration's {g}")

    def check_params(self, params, model):
        """The benchmark's weights have the program's parameter layout."""
        import jax

        from repro.nn.module import abstract_params

        want = abstract_params(model.specs())
        same = jax.tree.structure(want) == jax.tree.structure(params) and \
            all(a.shape == b.shape for a, b in zip(jax.tree.leaves(want),
                                                   jax.tree.leaves(params)))
        if not same:
            raise ValueError("the program's parameter layout differs from "
                             "bench/weights.py")

    # -- the window ------------------------------------------------------------

    def window_seconds(self):
        return min(self.seconds, TRACE_SECONDS) if self.trace \
            else self.seconds

    def profile(self):
        return _Profile(self)

    def memory_peak(self):
        if self.device is None:
            return 0
        return int(self.device.memory_stats()["peak_bytes_in_use"])


class _Profile:
    """The profiler around the window, when the run is traced."""

    def __init__(self, cell):
        self.cell = cell
        self.dir = None
        self.ann = None

    def __enter__(self):
        import jax

        if self.cell.trace:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)
        return self

    def mark_start(self):
        import jax

        if self.cell.trace:
            self.ann = jax.profiler.TraceAnnotation("bench.window")
            self.ann.__enter__()
        return time.perf_counter()

    def mark_end(self):
        if self.ann is not None:
            self.ann.__exit__(None, None, None)

    def __exit__(self, *exc):
        import jax

        from bench import trace

        if not self.cell.trace:
            return False
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                ops, spans = trace.load(
                    self.dir, getattr(runner(self.cell), "SPANS", ()))
                win = [(s, e) for n, s, e in spans if n == "bench.window"]
                if not win:
                    raise RuntimeError("no bench.window span in the trace")
                self.cell.trace_result = trace.reduce(ops, spans, *win[-1])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return False


def _reader(name):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def _listed(entry, workload):
    return "workloads" not in entry or workload in entry["workloads"]


def checks_of(readings, limits):
    """[(name, value, limit)] for every limited reading."""
    return [(k, readings.get(k, math.inf), lim) for k, lim in limits.items()]


def correct_of(readings, limits):
    """The verdict: every limited reading present, finite and within its
    limit, on at least one compared token."""
    return all(math.isfinite(v) and v <= lim
               for _, v, lim in checks_of(readings, limits)) \
        and readings.get("tokens_compared", 0) > 0


def result(cell, outcome, bench, device):
    """The result line's object, and the lines for standard error."""
    from bench import trace

    checks = checks_of(outcome["readings"], cell.limits)
    correct = correct_of(outcome["readings"], cell.limits)
    metrics = {}
    if not cell.trace:
        for m in bench["end_to_end"]:
            if not _listed(m, cell.name):
                continue
            v = outcome["setup_s"] if m["name"] == "setup_s" \
                else outcome["metrics"][m["name"]]
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        ctx = {"trace": cell.trace_result, "work": outcome["layer"],
               "peak": cell.peak}
        for m in bench["per_layer"]:
            if not _listed(m, cell.name):
                continue
            v = _reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    dev = {"platform": device.platform, "kind": device.device_kind,
           "count": outcome.get("chips", 1),
           "memory_peak_bytes": outcome["memory_peak_bytes"]}
    out = {"correct": bool(correct), "attempted": outcome["attempted"],
           "failed": outcome["failed"], "metrics": metrics, "device": dev}
    if cell.trace:
        tr = cell.trace_result
        dev["busy_s"], dev["window_s"] = tr["busy_s"], tr["window_s"]
        out["breakdown"] = {
            "device_ops": [[n, s] for n, s in
                           trace.grouped(tr["op_seconds"])[:10]],
            "idle_gaps": [[n, s] for n, s in tr["idle_gaps"][:10]]}
    out["checks"] = {k: {"value": v, "limit": lim} for k, v, lim in checks}
    lines = [f"check {k}: {v!r} (limit {lim!r})" for k, v, lim in checks]
    return out, lines


def enable_compile_cache():
    """JAX's persistent compilation cache at the fixed ``<checkout>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept."""
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def runner(cell):
    """The module ``bench.<kind>`` for the cell's traffic ``kind``."""
    kind = cell.traffic["kind"]
    if not (isinstance(kind, str) and kind.isidentifier()):
        raise SystemExit(f"traffic kind {kind!r} is not a plain identifier")
    try:
        return importlib.import_module("bench." + kind)
    except ModuleNotFoundError as e:
        if e.name != "bench." + kind:
            raise
        raise SystemExit(f"no runner for traffic kind {kind!r}: module "
                         f"bench.{kind} (bench/{kind}.py) is missing"
                         ) from None


def execute(cell):
    """Run the cell's runner; returns its outcome dict (the contract in
    this module's docstring)."""
    return runner(cell).run(cell)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = load_json("BENCHMARK.json")
    chips = {w["name"]: w["chips"] for w in bench["workloads"]}
    if args.workload not in chips:
        print(f"unknown workload {args.workload!r}", file=sys.stderr)
        return 2

    import jax

    from bench.peaks import peaks

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX platform is {devices[0].platform!r}",
              file=sys.stderr)
        return 2
    if len(devices) < chips[args.workload]:
        print(f"{args.workload} needs {chips[args.workload]} chips, JAX "
              f"sees {len(devices)}", file=sys.stderr)
        return 2
    try:
        peak = peaks(devices[0].device_kind)
    except KeyError as e:
        print(e, file=sys.stderr)
        return 2
    enable_compile_cache()
    cell = Cell.from_benchmark(args.workload, seed=args.seed,
                               seconds=args.seconds, trace=bool(args.trace),
                               device=devices[0], peak=peak)
    outcome = execute(cell)
    out, lines = result(cell, outcome, bench, devices[0])
    print("info " + json.dumps(outcome["info"]), file=sys.stderr)
    for line in lines:
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
