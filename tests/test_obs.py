"""Telemetry-layer suite: the zero-sync contract, end to end.

The claim under test: turning telemetry on changes NOTHING about the
computation — actions, poses, and metrics are bit-identical with the
registry enabled vs ``obs.NULL``, and no component compiles even one
extra program. Plus the instruments themselves: the log-bucket
histogram's percentile error bound, the Chrome-trace / Prometheus
exporters, the NaN-guard surfacing through the Trainer, straggler
decisions landing as events, and the committed bench records passing
the schema checker.
"""
import importlib.util
import json
import math
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import obs
from repro.data.pipeline import ShardedIterator
from repro.nn import module as nnm
from repro.nn.agent_sim import AgentSimConfig, AgentSimModel
from repro.runtime.monitor import StragglerPolicy
from repro.runtime.rollout import RolloutEngine
from repro.runtime.sim_server import SceneRequest, SimServer, poisson_drive
from repro.runtime.trainer import Trainer, TrainerConfig
from repro.scenarios import ScenarioConfig
from repro.scenarios.registry import generate_mixed

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))

SCEN = ScenarioConfig(num_map=8, num_agents=3, num_steps=6)
T_HIST = 3


def _model(seed=0):
    cfg = AgentSimConfig(d_model=32, num_layers=2, num_heads=2, head_dim=12,
                         d_ff=64, num_actions=SCEN.num_actions,
                         encoding="se2_fourier", attn_impl="ref")
    model = AgentSimModel(cfg)
    return model, nnm.init_params(model.specs(), jax.random.key(seed))


MODEL, PARAMS = _model()
SCENES = generate_mixed(5, 0, 4, SCEN)


# ---------------------------------------------------------------------------
# Histogram: the shared percentile sketch
# ---------------------------------------------------------------------------

def _nearest_rank(sorted_vals, q):
    return sorted_vals[max(1, math.ceil(q / 100.0 * len(sorted_vals))) - 1]


def test_histogram_percentile_error_bound():
    rng = np.random.default_rng(0)
    samples = rng.lognormal(mean=-6.0, sigma=1.5, size=2000)
    h = obs.Histogram("t")
    for v in samples:
        h.record(v)
    exact = np.sort(samples)
    for q in (1, 25, 50, 90, 99, 99.9):
        got, want = h.percentile(q), _nearest_rank(exact, q)
        assert abs(got / want - 1) <= h.max_rel_error + 1e-12, (q, got, want)
    assert h.count == 2000
    np.testing.assert_allclose(h.sum, samples.sum(), rtol=1e-9)
    assert h.min == samples.min() and h.max == samples.max()


def test_histogram_extremes_are_exact():
    h = obs.Histogram()
    for v in (0.5, 1.0, 3.0):
        h.record(v)
    assert h.percentile(0) == 0.5          # clamped to observed min
    assert h.percentile(100) == 3.0        # clamped to observed max


def test_histogram_zero_and_negative_underflow():
    h = obs.Histogram()
    for v in (-1.0, 0.0, 0.0, 1.0):
        h.record(v)
    assert h.count == 4 and h.zero_count == 3
    assert h.percentile(50) <= 0.0         # rank falls in the underflow
    assert h.percentile(100) == 1.0
    h2 = obs.Histogram()
    assert math.isnan(h2.percentile(50))   # empty -> NaN, not a crash
    h2.record(float("nan"))                # NaN samples are dropped
    assert h2.count == 0


def test_poisson_drive_returns_shared_histogram():
    srv = SimServer(MODEL, PARAMS, SCEN, num_slots=2, registry=obs.NULL)
    reqs = [SceneRequest(uid=i, tensors=s, t_hist=T_HIST)
            for i, s in enumerate(SCENES)]
    out = poisson_drive(srv, reqs, rate=0.5, seed=1, warmup_ticks=2)
    assert isinstance(out["latency"], obs.Histogram)
    assert out["ticks"] > 2
    # warmup ticks are excluded from the sketch but counted in "ticks"
    assert out["latency"].count == out["ticks"] - 2
    assert out["latency"].percentile(50) > 0


# ---------------------------------------------------------------------------
# Registry mechanics
# ---------------------------------------------------------------------------

def test_registry_instrument_identity_and_labels():
    r = obs.Registry()
    assert r.counter("c") is r.counter("c")
    assert r.counter("c", k=1) is not r.counter("c", k=2)
    r.counter("c", k=1).inc(3)
    snap = r.snapshot()
    by = {(c["name"], tuple(sorted(c["labels"].items())))
          for c in snap["counters"]}
    assert ("c", (("k", "1"),)) in by     # label values stringify


def test_null_registry_records_nothing():
    n0 = len(obs.NULL.events())
    with obs.NULL.span("x"):
        pass
    obs.NULL.counter("c").inc()
    obs.NULL.gauge("g").set(1)
    obs.NULL.histogram("h").record(1.0)
    obs.NULL.event("e")
    assert len(obs.NULL.events()) == n0
    assert obs.NULL.snapshot()["counters"] == []


def test_span_records_histogram_and_event():
    r = obs.Registry()
    with r.span("work", phase="a"):
        pass
    h = r.histogram("work.seconds", phase="a")
    assert h.count == 1
    (ev,) = [e for e in r.events() if e["ph"] == "X"]
    assert ev["name"] == "work" and ev["dur"] >= 0
    assert ev["args"] == {"phase": "a"}
    # observe_span: same shape, caller-measured interval
    r.observe_span("work", 0.0, 1.0, phase="a")
    assert h.count == 2
    # args ride the trace event only: no histogram series of their own
    with r.span("work", args={"uid": 7}, phase="a"):
        pass
    assert h.count == 3
    assert r.events()[-1]["args"] == {"phase": "a", "uid": 7}


def test_trace_ring_drops_oldest_half_at_capacity():
    r = obs.Registry(trace_capacity=10)
    for i in range(12):
        r.event("e", i=i)
    assert r.dropped_events == 5
    assert len(r.events()) <= 10
    assert r.events()[-1]["args"]["i"] == 11    # newest survive


# ---------------------------------------------------------------------------
# Exporters
# ---------------------------------------------------------------------------

def _busy_registry():
    r = obs.Registry()
    r.counter("reqs", route="a").inc(3)
    r.gauge("occ").set(0.5)
    for v in (1e-3, 2e-3, 4e-3):
        r.histogram("lat.seconds").record(v)
    with r.span("tick"):
        pass
    r.event("evict", uid=7)
    return r


def test_chrome_trace_roundtrip(tmp_path):
    r = _busy_registry()
    path = str(tmp_path / "t.trace.jsonl")
    obs.write_chrome_trace(r, path)
    with open(path) as f:
        whole = json.load(f)               # valid JSON array for Perfetto
    again = obs.read_chrome_trace(path)
    assert whole == again
    for ev in whole:
        assert "name" in ev and "ph" in ev
    names = [e["name"] for e in whole]
    assert names[0] == "process_name"       # metadata first
    assert names[-1] == obs.SNAPSHOT_EVENT  # snapshot last
    snap = whole[-1]["args"]["snapshot"]
    assert any(c["name"] == "reqs" for c in snap["counters"])
    assert any(h["name"] == "lat.seconds" for h in snap["histograms"])


def test_prometheus_text_exposition():
    text = obs.prometheus_text(_busy_registry())
    assert 'reqs_total{route="a"} 3.0' in text
    assert "occ 0.5" in text
    assert "lat_seconds_count 3" in text
    # classic histogram: cumulative buckets ending at +Inf == count
    le_lines = [ln for ln in text.splitlines()
                if ln.startswith("lat_seconds_bucket")]
    counts = [float(ln.rsplit(" ", 1)[1]) for ln in le_lines]
    assert counts == sorted(counts), "bucket series must be cumulative"
    assert 'le="+Inf"' in le_lines[-1] and counts[-1] == 3


# ---------------------------------------------------------------------------
# No-perturbation: obs on/off bit-parity + zero extra compilations
# ---------------------------------------------------------------------------

MATRIX = [("float32", "xla"), ("int8", "ref")]


@pytest.mark.parametrize("cache_dtype,impl", MATRIX,
                         ids=[f"{d}-{i}" for d, i in MATRIX])
def test_sim_server_obs_on_off_bit_identical(cache_dtype, impl):
    results = {}
    for name, reg in (("on", obs.Registry()), ("off", obs.NULL)):
        srv = SimServer(MODEL, PARAMS, SCEN, num_slots=2,
                        cache_dtype=cache_dtype, decode_impl=impl,
                        registry=reg)
        reqs = [SceneRequest(uid=i, tensors=s, t_hist=T_HIST, seed=9)
                for i, s in enumerate(SCENES)]
        poisson_drive(srv, reqs, rate=0.7, seed=3)
        # retrace guard: telemetry must not add even one compilation
        assert srv.tick_traces == 1, f"{name}: tick retraced"
        assert srv.admit_traces == 1, f"{name}: admit retraced"
        results[name] = srv.done
    assert results["on"].keys() == results["off"].keys()
    for uid in results["on"]:
        a, b = results["on"][uid], results["off"][uid]
        np.testing.assert_array_equal(a.actions, b.actions)
        np.testing.assert_array_equal(a.future, b.future)


@pytest.mark.parametrize("cache_dtype,impl", MATRIX,
                         ids=[f"{d}-{i}" for d, i in MATRIX])
def test_rollout_engine_obs_on_off_bit_identical(cache_dtype, impl):
    outs = {}
    for name, reg in (("on", obs.Registry()), ("off", obs.NULL)):
        eng = RolloutEngine(MODEL, PARAMS, SCEN, num_slots=4,
                            cache_dtype=cache_dtype, decode_impl=impl,
                            registry=reg)
        fut = eng.run(SCENES, t_hist=T_HIST, n_samples=1, seed=9)
        # zero extra compilations: one program per jitted entry point
        assert eng._prefill._cache_size() == 1, f"{name}: prefill retraced"
        assert eng._step._cache_size() == 1, f"{name}: step retraced"
        outs[name] = (fut, eng.last_actions)
    np.testing.assert_array_equal(outs["on"][0], outs["off"][0])
    np.testing.assert_array_equal(outs["on"][1], outs["off"][1])


def test_sim_server_telemetry_content():
    reg = obs.Registry()
    srv = SimServer(MODEL, PARAMS, SCEN, num_slots=2, registry=reg)
    reqs = [SceneRequest(uid=i, tensors=s, t_hist=T_HIST)
            for i, s in enumerate(SCENES)]
    poisson_drive(srv, reqs, rate=0.7, seed=3)
    srv.evict(999)                         # miss: no event
    stats = srv.stats()
    assert reg.counter("sim_server.ticks").value == stats["ticks"]
    assert reg.counter("sim_server.admitted").value == stats["admitted"]
    assert reg.counter("sim_server.tick_traces").value == 1
    assert reg.histogram("sim_server.queue_wait.seconds").count \
        == len(SCENES)
    assert reg.histogram("sim_server.first_action.seconds").count \
        == len(SCENES)
    # per-tick gauges end drained: nothing resident, nothing queued
    assert reg.gauge("sim_server.occupancy").value == 0.0
    assert reg.gauge("sim_server.resident").value == 0.0
    assert reg.gauge("sim_server.slab_bytes").value > 0
    tick_spans = [e for e in reg.events()
                  if e.get("ph") == "X" and e["name"] == "sim_server.tick"]
    assert len(tick_spans) == int(stats["ticks"])


# ---------------------------------------------------------------------------
# Program spans on the profiler clock; the tick's layers named in its HLO
# ---------------------------------------------------------------------------

TICK_CHILDREN = ("sim_server.assemble", "sim_server.dispatch",
                 "sim_server.drain")


def _profiled_host_events(trace_dir, prefix):
    """(name, start_s, end_s, stats) of the host events whose name starts
    with ``prefix`` in the profiler trace under ``trace_dir``."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host"):
            for line in plane.lines:
                out += [(e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9, dict(e.stats))
                        for e in line.events if e.name.startswith(prefix)]
    return sorted(out, key=lambda ev: ev[1])


def test_sim_server_spans_nest_on_the_profiler_clock(tmp_path):
    reg = obs.Registry()
    srv = SimServer(MODEL, PARAMS, SCEN, num_slots=2, registry=reg)
    for i, s in enumerate(SCENES[:3]):
        srv.submit(SceneRequest(uid=i, tensors=s, t_hist=T_HIST))
    srv.tick()                             # compiles; admits lanes 0 and 1
    ticks0 = srv.ticks
    with jax.profiler.trace(str(tmp_path)):
        # lane 2 is admitted when a lane retires at its horizon
        while srv.admitted < 3:
            srv.tick()
        srv.run_until_drained()
        n_ticks = srv.ticks - ticks0
        assert srv.tick() is False         # an idle poll
    assert reg.histogram("sim_server.tick.seconds").count == srv.ticks
    for name in TICK_CHILDREN:
        assert reg.histogram(name + ".seconds").count == srv.ticks

    evs = _profiled_host_events(str(tmp_path), "sim_server.")
    ticks = [e for e in evs if e[0] == "sim_server.tick"]
    assert len(ticks) == n_ticks           # the idle poll opened no span
    for name, t0, t1, _ in evs:
        if name == "sim_server.tick":
            continue
        # every child lies inside exactly one tick, on the same clock
        assert sum(s <= t0 and t1 <= e for _, s, e, _ in ticks) == 1, name
    for _, s, e, _ in ticks:
        inside = [n for n, t0, t1, _ in evs if s <= t0 and t1 <= e
                  and n in TICK_CHILDREN]
        assert sorted(inside) == sorted(TICK_CHILDREN)
    # an admission's span carries its lane: uid and slot
    (admit,) = [e for e in evs if e[0] == "sim_server.admit"]
    assert admit[3]["uid"] == 2 and admit[3]["slot"] in (0, 1)


def _tick_hlo(encoding):
    """The tick of a tiny two-slot server, compiled: its HLO text with each
    op's metadata (the ``named_scope`` path)."""
    cfg = AgentSimConfig(d_model=32, num_layers=2, num_heads=2, head_dim=12,
                         d_ff=64, num_actions=SCEN.num_actions,
                         encoding=encoding, attn_impl="ref")
    model = AgentSimModel(cfg)
    params = nnm.init_params(model.specs(), jax.random.key(0))
    srv = SimServer(model, params, SCEN, num_slots=2, registry=obs.NULL)
    b, a = srv.num_slots, SCEN.num_agents
    args = (params, srv.cache, srv.state,
            jnp.zeros((b, a, SCEN.agent_feat_dim)), jnp.zeros((b, a, 3)),
            jnp.ones((b, a), bool), jnp.zeros((b,), jnp.int32),
            jnp.ones((b,), bool), jnp.zeros((b,), bool))
    hlo = jax.jit(srv._tick_impl).lower(*args).compile().as_text()
    return hlo, srv.cache["k"].shape


@pytest.mark.parametrize("encoding", ["se2_fourier", "absolute"])
def test_tick_hlo_names_its_layers(encoding):
    hlo, k_shape = _tick_hlo(encoding)
    k_type = "f32[" + ",".join(map(str, k_shape)) + "]"
    writes = [ln for ln in hlo.splitlines()
              if " dynamic-update-slice(" in ln and f"= {k_type}" in ln]
    assert writes and all("agent_sim.cache_write" in ln for ln in writes)
    for scope in ("agent_sim.decode_attention", "agent_sim.mlp",
                  "agent_sim.head", "sim_server.sample",
                  "sim_server.kinematics"):
        assert scope in hlo, scope
    # the absolute baseline has no SE(2) transform to name
    assert ("agent_sim.se2_transform" in hlo) == (encoding == "se2_fourier")


# ---------------------------------------------------------------------------
# Trainer: NaN-guard surfacing + step spans
# ---------------------------------------------------------------------------

def _nanny_step(nan_steps):
    calls = {"n": 0}

    def step(params, opt_state, batch):
        loss = (jnp.float32(float("nan")) if calls["n"] in nan_steps
                else jnp.float32(1.0 / (1 + calls["n"])))
        calls["n"] += 1
        return params, opt_state, {"loss": loss}

    return step


def test_trainer_surfaces_nan_skips(tmp_path):
    data = ShardedIterator(
        lambda seed, idx, b: {"x": np.zeros((b, 1), np.float32)},
        batch_size=2, seed=0)
    reg = obs.Registry()
    payloads = []
    tr = Trainer(_nanny_step({1, 5}), {"w": jnp.zeros(2)}, {}, data,
                 str(tmp_path),
                 TrainerConfig(total_steps=8, ckpt_every=100, log_every=2,
                               max_consecutive_nans=4),
                 metrics_cb=lambda s, m: payloads.append((s, m)),
                 registry=reg)
    out = tr.run()
    data.close()
    assert out["status"] == "done"
    # run summary carries the skip count (satellite: silent discards ban)
    assert out["nan_skipped"] == 2
    assert reg.counter("trainer.nan_skipped").value == 2
    # every metrics payload reports the counts
    assert payloads and all("nan_skipped_total" in m and
                            "nan_consecutive" in m for _, m in payloads)
    assert payloads[-1][1]["nan_skipped_total"] == 2
    # 8 total steps dispatched (6 applied + 2 skipped), each under a span
    assert reg.histogram("trainer.step.seconds").count == 8
    assert reg.histogram("trainer.checkpoint.seconds").count >= 1


def test_trainer_halt_emits_event(tmp_path):
    data = ShardedIterator(
        lambda seed, idx, b: {"x": np.zeros((b, 1), np.float32)},
        batch_size=2, seed=0)
    reg = obs.Registry()
    tr = Trainer(_nanny_step(set(range(99))), {"w": jnp.zeros(2)}, {}, data,
                 str(tmp_path),
                 TrainerConfig(total_steps=50, ckpt_every=100, log_every=100,
                               max_consecutive_nans=3),
                 registry=reg)
    with pytest.raises(FloatingPointError):
        tr.run()
    data.close()
    halts = [e for e in reg.events() if e["name"] == "trainer.halt"]
    assert len(halts) == 1 and halts[0]["args"]["consecutive"] == 3


def test_straggler_policy_exports_decision():
    reg = obs.Registry()
    p = StragglerPolicy(straggler_factor=1.5, min_samples=2, registry=reg)
    warm = {0: 10, 1: 10}
    assert p.evaluate({0: 1.0, 1: 4.0}, warm) == [1]
    assert reg.gauge("straggler.rank_median_s", rank=1).value == 4.0
    assert reg.gauge("straggler.rank_samples", rank=0).value == 10
    assert reg.counter("straggler.flag_decisions").value == 1
    (ev,) = [e for e in reg.events() if e["name"] == "straggler.flagged"]
    assert ev["args"]["ranks"] == "1"
    assert ev["args"]["fleet_median_s"] == 1.0
    # a no-flag evaluation updates gauges but emits no event
    assert p.evaluate({0: 1.0, 1: 1.1}, warm) == []
    assert reg.counter("straggler.flag_decisions").value == 1


# ---------------------------------------------------------------------------
# obs_report CLI + bench schema
# ---------------------------------------------------------------------------

def test_obs_report_renders_trace(tmp_path, capsys):
    from repro.launch import obs_report
    reg = obs.Registry()
    srv = SimServer(MODEL, PARAMS, SCEN, num_slots=2, registry=reg)
    reqs = [SceneRequest(uid=i, tensors=s, t_hist=T_HIST)
            for i, s in enumerate(SCENES)]
    poisson_drive(srv, reqs, rate=0.7, seed=3)
    path = str(tmp_path / "run.trace.jsonl")
    obs.write_chrome_trace(reg, path)

    assert obs_report.main([path]) == 0
    text = capsys.readouterr().out
    for needle in ("== spans", "== compilations", "== gauges",
                   "sim_server.tick", "sim_server.admit_traces",
                   "sim_server.occupancy"):
        assert needle in text, f"report missing {needle!r}"

    assert obs_report.main([path, "--json"]) == 0
    agg = json.loads(capsys.readouterr().out)
    assert agg["spans"]["sim_server.tick"]["count"] == srv.ticks
    assert agg["snapshot"]["counters"]


def test_obs_report_renders_committed_sample(capsys):
    from repro.launch import obs_report
    sample = os.path.join(ROOT, "docs", "samples", "obs_sample.trace.jsonl")
    assert os.path.exists(sample), "committed sample trace missing"
    assert obs_report.main([sample]) == 0
    text = capsys.readouterr().out
    assert "sim_server.tick" in text and "== histograms" in text


def _load_bench_schema():
    spec = importlib.util.spec_from_file_location(
        "bench_schema", os.path.join(ROOT, "benchmarks", "bench_schema.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_committed_bench_records_pass_schema():
    bs = _load_bench_schema()
    import glob
    records = sorted(glob.glob(os.path.join(ROOT, "BENCH_*.json")))
    assert records, "no committed bench records found"
    problems = [p for path in records for p in bs.check_file(path)]
    assert problems == []


def test_bench_schema_catches_broken_record(tmp_path):
    bs = _load_bench_schema()
    with open(os.path.join(ROOT, "BENCH_serve.json")) as f:
        rec = json.load(f)
    row = next(iter(rec["slot_counts"].values()))
    del row["tick_p50_ms"]
    row["tick_p99_ms"] = float("nan")
    bad = tmp_path / "BENCH_serve_broken.json"
    bad.write_text(json.dumps(rec).replace("NaN", "null"))
    # null p99 -> type problem; missing p50 -> missing-key problem
    problems = bs.check_file(str(bad))
    assert any("tick_p50_ms" in p for p in problems)
    assert any("tick_p99_ms" in p for p in problems)
