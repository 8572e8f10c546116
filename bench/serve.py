"""Serving cells: ``SimServer`` driven on the wall clock.

The configuration names the server's slot count and cache; the traffic file
names the scene caps, the request shape (samples per scene request, history
and horizon) and the arrival process:

* ``saturated``: a backlog of ``backlog_requests`` queued requests is kept
  at all times. With ``stagger`` the slots are first filled one lane at a
  time, evenly over one lane's life in ticks, so that in the steady state
  the slots admit at staggered ticks, as a long-running server's do,
  rather than all at once;
* ``poisson``: requests arrive as a Poisson process at ``rate`` requests a
  second on the wall clock, whether or not the server keeps up (open loop).

Set-up warms up in the traffic itself until the fill is done and a slot has
retired a lane and admitted the next, so the window starts in the steady
cycle. Every lane is timed from its scheduled arrival. The loop records
when each lane's actions reach the host (after each ``tick()``, from the
server's in-flight lane table), which gives the rollout steps delivered in
the window, the gap between a lane's consecutive actions, and each lane's
first action.

The configuration's ``drain_lag`` (default 1) is how many ticks the server
keeps dispatched ahead of the one whose actions the host waits for, so a
host stall shorter than that leaves the chip fed. The window starts with
nothing outstanding and, when its time is up, dispatches nothing more and
waits for every tick it sent, recording each tick's actions as they land:
the window counts all the work it dispatched, over all the time it took.

After the window, lanes still queued in a saturated cell are cancelled (they
were never attempted); every other lane is driven to its end. Then the
server is freed and a sample of finished lanes, drawn from the seed, is
compared with the reference (``check.serve_lanes``).
"""
from __future__ import annotations

import collections
import contextlib
import gc
import time

import jax
import numpy as np

from bench import check, work
from bench.traffic.scenes import make_scenes
from bench.weights import make_params

#: longest a run waits after the window for lanes to finish
DRAIN_LIMIT_S = 60.0
#: the server's host spans (``SimServer.tick`` and its children), kept in
#: a traced run's reduction
SPANS = ("sim_server.",)


def build(cell):
    """The program's model and server for this cell, and the scene pool."""
    from repro import obs
    from repro.configs import get_sim_arch
    from repro.nn.agent_sim import AgentSimModel
    from repro.runtime.sim_server import SimServer
    from repro.scenarios.core import ScenarioConfig

    model_cfg, shape, tr = cell.model, cell.traffic["scene"], cell.traffic
    model = AgentSimModel(cell.program_config(get_sim_arch))
    scen = ScenarioConfig(num_map=shape["num_map"],
                          num_agents=shape["num_agents"],
                          num_steps=shape["num_steps"])
    cell.check_grid(scen)
    params = make_params(model_cfg, cell.seed)
    cell.check_params(params, model)
    reg = obs.Registry()
    srv = SimServer(model, params, scen, num_slots=cell.config["serve_slots"],
                    cache_dtype=cell.cache_dtype,
                    drain_lag=cell.config.get("drain_lag", 1), registry=reg)
    pool = make_scenes(cell.seed, shape, cell.grid, tr["scene_pool"])
    return srv, reg, pool


class Lanes:
    """Host arrival times of every lane's actions."""

    def __init__(self, srv):
        self.srv = srv
        self.sched = {}          # uid -> scheduled arrival
        self.pool_index = {}     # uid -> scene pool index
        self.seen = {}           # uid -> actions on the host so far
        self.first = {}          # uid -> first action time
        self.last = {}           # uid -> latest action time
        self.gaps = []           # (end time, gap seconds)
        self.arrived = []        # (time, actions)
        self.inflight = set()

    def record(self, now):
        buf = self.srv._buf      # uid -> {"filled": actions drained, ...}
        for uid, b in buf.items():
            self._land(uid, b["filled"], now)
        for uid in self.inflight - buf.keys():
            res = self.srv.done.get(uid)
            if res is not None and res.status == "ok":
                self._land(uid, res.t_total - res.t_hist, now)
        self.inflight = set(buf)

    def _land(self, uid, filled, now):
        new = filled - self.seen.get(uid, 0)
        if new <= 0:
            return
        self.seen[uid] = filled
        self.arrived.append((now, new))
        if uid in self.last:
            self.gaps.append((now, now - self.last[uid]))
        else:
            self.first[uid] = now
        self.gaps += [(now, 0.0)] * (new - 1)
        self.last[uid] = now


class Traffic:
    """Arrivals of scene requests; each request is ``samples`` lanes of one
    pool scene with its own ``scene_id``."""

    def __init__(self, cell, srv, lanes, pool):
        from repro.runtime.sim_server import SceneRequest

        self.req_cls = SceneRequest
        self.tr = cell.traffic
        self.arr = self.tr["arrivals"]
        self.srv, self.lanes, self.pool = srv, lanes, pool
        self.rng = np.random.default_rng([cell.seed, 1])
        self.req_seed = int(self.rng.integers(0, 2 ** 31 - 1))
        self.requests = 0
        self.made = collections.deque()     # lanes made, not yet submitted
        self.next_at = None
        self.lag = []            # submit time - scheduled arrival, a lane
        self.open = True
        stagger = self.arr.get("stagger", False)
        self.fill = srv.num_slots if stagger else 0   # lanes still to stagger
        self.fill_every = self.tr["t_total"] / srv.num_slots
        self.fill_tick0 = None

    def _next_lane(self):
        if not self.made:
            k = self.requests
            for j in range(self.tr["samples"]):
                self.made.append(self.req_cls(
                    uid=k * self.tr["samples"] + j,
                    tensors=self.pool[k % len(self.pool)],
                    t_hist=self.tr["t_hist"], t_total=self.tr["t_total"],
                    seed=self.req_seed, scene_id=k, sample_id=j))
            self.requests += 1
        return self.made.popleft()

    def _submit(self, sched, now, lanes=1):
        for _ in range(lanes):
            req = self._next_lane()
            self.srv.submit(req)
            self.lanes.sched[req.uid] = sched
            self.lanes.pool_index[req.uid] = req.scene_id % len(self.pool)
            self.lag.append(now - sched)

    def prime(self, now):
        """The first lanes (a saturated cell's whole backlog unless it
        staggers), before the arrival clock starts: its first tick
        compiles."""
        if self.fill:
            self.fill_tick0 = self.srv.ticks
            self._submit(now, now)
            self.fill -= 1
        elif self.arr["process"] == "saturated":
            self.offer(now)
        else:
            self._submit(now, now, self.tr["samples"])

    def start(self, now):
        if self.arr["process"] == "poisson":
            self.next_at = now + self.rng.exponential(1.0 / self.arr["rate"])

    def steady(self):
        """The fill is done and some slot has retired a lane and admitted
        the next."""
        return not self.fill and self.srv.admitted > self.srv.num_slots

    def offer(self, now):
        """Submit what is due at ``now``; returns the next arrival time
        (None when arrivals do not wait on the clock)."""
        if not self.open:
            return None
        if self.arr["process"] == "saturated":
            if self.fill:
                done = self.srv.num_slots - self.fill
                if self.srv.ticks - self.fill_tick0 \
                        >= round(done * self.fill_every):
                    self._submit(now, now)
                    self.fill -= 1
                return None
            want = self.arr["backlog_requests"] * self.tr["samples"]
            while len(self.srv.queue) < want:
                self._submit(now, now)
            return None
        if self.next_at is None:             # the clock has not started
            return None
        while self.next_at <= now:
            self._submit(self.next_at, now, self.tr["samples"])
            self.next_at += self.rng.exponential(1.0 / self.arr["rate"])
        return self.next_at


def _busy(srv):
    return bool(srv.queue) or any(s.req is not None for s in srv.slots)


def _occupied(srv):
    return [(s.req.uid, s.t) if s.req is not None else None
            for s in srv.slots]


def _tick_live_rows(srv, before, after, m, a):
    """Rows each active slot's decode read this tick (its cursor after
    the tick's rows), from the slot table before and after the tick."""
    rows = []
    for pre, post in zip(before, after):
        if post is not None:
            rows.append(m + post[1] * a)
        elif pre is not None:            # retired at the end of the tick
            rows.append(m + (pre[1] + 1) * a)
    return rows


class Watch:
    """What the host saw in the window, to find where a stall went: each
    tick's wall and process CPU seconds and admissions, the programs JAX
    compiled or loaded (its compile requests) and the garbage collector's
    pauses. Reported under ``info``; no metric reads it."""

    seen = 0                     # compile requests since the first Watch
    listening = False

    def __init__(self):
        if not Watch.listening:
            jax.monitoring.register_event_listener(Watch._request)
            Watch.listening = True
        self.ticks = []          # (end, wall s, cpu s, admissions)
        self.gc = []
        self._gc0 = None

    @staticmethod
    def _request(name, **kw):
        if name == "/jax/compilation_cache/compile_requests_use_cache":
            Watch.seen += 1

    def _collect(self, phase, info):
        if phase == "start":
            self._gc0 = time.perf_counter()
        elif self._gc0 is not None:
            self.gc.append(time.perf_counter() - self._gc0)

    def __enter__(self):
        self.seen0 = Watch.seen
        gc.callbacks.append(self._collect)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._collect)
        self.requests = Watch.seen - self.seen0
        return False

    def summary(self, t0):
        wall = np.array([w for _, w, _, _ in self.ticks])
        med = float(np.median(wall)) if wall.size else 0.0
        long = [[round(e - t0, 3), round(1e3 * w, 1), round(1e3 * c, 1), n]
                for e, w, c, n in self.ticks if w > 1.5 * med]
        return {"compile_requests": self.requests,
                "gc_pause_ms": [1e3 * sum(self.gc), 1e3 * max(self.gc,
                                                              default=0.0)],
                "tick_median_ms": 1e3 * med,
                "long_ticks": sorted(long, key=lambda x: -x[1])[:10]}


def drive(cell, srv, traffic, lanes, *, until, annotate, tick_log=None,
          watch=None):
    """Run the loop until ``until(now)`` is true after a tick; returns the
    time the last tick ended. ``tick_log`` (a list) receives each tick's
    live rows per active slot and its admissions; ``watch`` (a ``Watch``)
    each tick's times."""
    span = jax.profiler.TraceAnnotation if annotate else \
        (lambda name: contextlib.nullcontext())
    shape = cell.traffic["scene"]
    m, a = shape["num_map"], shape["num_agents"]
    now, cpu = time.perf_counter(), time.process_time()
    while True:
        with span("bench.submit"):
            next_at = traffic.offer(now)
        if _busy(srv):
            before = _occupied(srv) if tick_log is not None else None
            admitted = srv.admitted
            with span("bench.tick"):
                srv.tick()
            last, now = now, time.perf_counter()
            lanes.record(now)
            if watch is not None:
                c = time.process_time()
                watch.ticks.append((now, now - last, c - cpu,
                                    srv.admitted - admitted))
                cpu = c
            if tick_log is not None:
                tick_log.append((_tick_live_rows(srv, before, _occupied(srv),
                                                 m, a),
                                 srv.admitted - admitted))
            if until(now):
                return now
        elif next_at is not None:
            with span("bench.idle"):
                time.sleep(max(0.0, next_at - time.perf_counter()))
            now, cpu = time.perf_counter(), time.process_time()
        else:
            return now


def settle(srv, lanes):
    """Wait for every tick the server has dispatched, landing each one's
    actions on the host in turn; returns the time after the wait."""
    while srv._pending:
        srv._drain(len(srv._pending) - 1)
        lanes.record(time.perf_counter())
    jax.block_until_ready((srv.cache, srv.state))
    return time.perf_counter()


def run(cell):
    t_setup = time.perf_counter()
    srv, reg, pool = build(cell)
    lanes = Lanes(srv)
    traffic = Traffic(cell, srv, lanes, pool)

    # compile the admission and the tick on the first lanes, then start
    # the arrival clock and warm up in the traffic itself until it is
    # steady
    traffic.prime(time.perf_counter())
    drive(cell, srv, traffic, lanes, until=lambda now: True, annotate=False)
    traffic.start(time.perf_counter())
    drive(cell, srv, traffic, lanes, annotate=False,
          until=lambda now: traffic.steady())
    compiles0 = (srv.tick_traces, srv.admit_traces)
    setup_s = time.perf_counter() - t_setup

    seconds = cell.window_seconds()
    tick_log = [] if cell.trace else None
    with cell.profile() as prof, Watch() as watch:
        settle(srv, lanes)
        t0 = prof.mark_start()
        admitted0 = srv.admitted
        admit0 = _hist(reg, "sim_server.admit.seconds")
        drive(cell, srv, traffic, lanes, annotate=cell.trace,
              tick_log=tick_log, watch=watch,
              until=lambda now: now - t0 >= seconds)
        t_end = settle(srv, lanes)
        admit1 = _hist(reg, "sim_server.admit.seconds")
        prof.mark_end()
    window = t_end - t0
    admitted_in_window = srv.admitted - admitted0
    queued_at_close = len(srv.queue)
    compiles = (srv.tick_traces - compiles0[0], srv.admit_traces
                - compiles0[1])

    # close the window: cancel what was never admitted in a saturated cell,
    # drive everything else to its end
    traffic.open = False
    if traffic.arr["process"] == "saturated":
        for r in list(srv.queue):
            srv.evict(r.uid)
            lanes.sched.pop(r.uid)
    t_drain = time.perf_counter()
    drive(cell, srv, traffic, lanes, annotate=False,
          until=lambda now: now - t_drain > DRAIN_LIMIT_S)
    srv.flush()
    run_end = time.perf_counter()
    lanes.record(run_end)
    peak = cell.memory_peak()

    attempted = [u for u in lanes.sched if lanes.sched[u] <= t_end]
    ok = {u for u in attempted
          if u in srv.done and srv.done[u].status == "ok"}
    gaps = [g for t, g in lanes.gaps if t0 <= t <= t_end]
    steps = sum(n for t, n in lanes.arrived if t0 <= t <= t_end)
    firsts = [lanes.first.get(u, run_end) - s
              for u, s in lanes.sched.items() if t0 <= s < t_end]
    metrics = {
        "rollout_steps_per_s": steps / window,
        "step_gap_p95_ms": 1e3 * float(np.percentile(gaps, 95)),
    }
    if firsts:
        metrics["first_action_p95_ms"] = 1e3 * float(np.percentile(firsts,
                                                                   95))
    layer = {}
    if cell.trace:
        layer = _layer_counts(cell, tick_log, admit0, admit1)
    t_fut = cell.traffic["t_total"] - cell.traffic["t_hist"]
    finished = sum(1 for u, t in lanes.last.items()
                   if t0 <= t <= t_end and lanes.seen[u] == t_fut)
    info = {"slots": srv.num_slots, "window_s": window,
            "lanes_finished_per_s": finished / window,
            "queued_at_close": queued_at_close,
            "window_compiles": list(compiles), "ticks": srv.ticks,
            "lanes_done": len(ok), "rollout_steps": steps,
            "admitted_in_window": admitted_in_window,
            "window_host": watch.summary(t0),
            "generator_lag_p95_ms": 1e3 * float(np.percentile(traffic.lag,
                                                              95))}

    sample = _sample(cell, srv, lanes, sorted(ok), pool, traffic)
    del srv, traffic, lanes
    gc.collect()
    readings, control = check.serve_lanes(cell, sample, control=cell.control)
    return {"setup_s": setup_s, "metrics": metrics, "layer": layer,
            "attempted": len(attempted), "failed": len(attempted) - len(ok),
            "readings": readings, "control": control,
            "memory_peak_bytes": peak, "info": info}


def _hist(reg, name):
    h = reg.histogram(name)
    return h.count, h.sum


def _layer_counts(cell, tick_log, admit0, admit1):
    """Work of the ticks in the traced window, for the per-layer readers."""
    shape = cell.traffic["scene"]
    m, a = shape["num_map"], shape["num_agents"]
    model = cell.model
    flops = nbytes = model_flops = 0.0
    for rows, admits in tick_log:
        f, b = work.decode_work(model, cell.cache_dtype, a, rows)
        flops, nbytes = flops + f, nbytes + b
        model_flops += work.tick_model_flops(model, a,
                                             model["agent_feat_dim"], rows)
        f, b = work.decode_work(model, cell.cache_dtype, m, [m] * admits)
        flops, nbytes = flops + f, nbytes + b
        model_flops += admits * work.admit_model_flops(
            model, m, model["map_feat_dim"])
    n, s = admit1[0] - admit0[0], admit1[1] - admit0[1]
    return {"decode_flops": flops, "decode_bytes": nbytes,
            "model_flops": model_flops, "ticks": len(tick_log),
            "admit_host_s": (s / n) if n else None}


def _sample(cell, srv, lanes, ok, pool, traffic):
    """Finished lanes drawn from the seed, the longest first."""
    rng = np.random.default_rng([cell.seed, 2])
    n = min(cell.traffic["check_lanes"], len(ok))
    if not n:
        return []
    longest = max(ok, key=lambda u: srv.done[u].t_total - srv.done[u].t_hist)
    rest = [u for u in ok if u != longest]
    pick = [longest] + list(rng.choice(rest, n - 1, replace=False)) \
        if n > 1 else [longest]
    out = []
    for uid in pick:
        res = srv.done[int(uid)]
        out.append({"scene": pool[lanes.pool_index[int(uid)]],
                    "t_hist": res.t_hist, "t_total": res.t_total,
                    "future": res.future, "actions": res.actions,
                    "req_seed": traffic.req_seed,
                    "scene_id": int(uid) // cell.traffic["samples"],
                    "sample_id": int(uid) % cell.traffic["samples"]})
    return out
