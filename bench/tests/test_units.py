"""Trace reduction, work counts and the peaks table, on small cases worked
out by hand."""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import peaks, trace, work  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

SE2 = {"num_layers": 6, "num_heads": 8, "head_dim": 24, "d_model": 256,
       "d_ff": 1024, "num_actions": 63, "encoding": "se2_fourier",
       "fourier_terms": 12}
ABS = dict(SE2, encoding="absolute")


# -- trace reduction ------------------------------------------------------------

OPS = [("fusion.1", 0.0, 1.0), ("_decode_kernel", 0.5, 2.0),
       ("fusion.1", 4.0, 5.0), ("copy", 7.0, 7.5)]
SPANS = [("bench.window", 0.0, 10.0), ("bench.tick", 0.0, 3.0),
         ("bench.tick", 3.5, 8.0), ("bench.submit", 5.2, 6.0)]


def test_busy_is_the_union_of_op_intervals():
    assert trace.busy_seconds(OPS) == pytest.approx(2.0 + 1.0 + 0.5)
    assert trace.merge([(0, 1), (0.5, 2), (3, 4)]) == [(0, 2), (3, 4)]


def test_time_by_name_sums_each_op():
    got = dict(trace.time_by_name(OPS))
    assert got == {"fusion.1": 2.0, "_decode_kernel": 1.5, "copy": 0.5}
    assert list(dict(trace.time_by_name(OPS))) == ["fusion.1",
                                                   "_decode_kernel", "copy"]


def test_idle_gaps_go_to_the_innermost_open_host_span():
    gaps = dict(trace.idle_gaps(OPS, SPANS, 0.0, 10.0))
    # gaps [2, 4] (mid 3.0, where the first tick has just ended) and
    # [7.5, 10] see only the window open
    assert gaps["bench.window"] == pytest.approx(2.0 + 2.5)
    # [5, 7] mid 6.0: tick [3.5, 8) open, submit ended at 6.0 -> tick
    assert gaps["bench.tick"] == pytest.approx(2.0)
    assert sum(gaps.values()) == pytest.approx(10.0 - 3.5)


def test_reduce_clips_to_the_window_and_averages_devices():
    ops = {"/device:TPU:0": OPS, "/device:TPU:1": [("x", 0.0, 10.0)]}
    r = trace.reduce(ops, SPANS, 1.0, 6.0)
    assert r["window_s"] == 5.0
    assert r["busy_s"] == pytest.approx(((2.0 - 1.0) + 1.0 + 5.0) / 2)
    assert r["op_seconds"]["_decode_kernel"] == pytest.approx(1.0)


def _unpack(tmp_path, name):
    """A gzipped trace under ``data/`` laid out as the profiler writes it."""
    import gzip
    import shutil

    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, name)) as src, \
            open(prof / "ticks.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(tmp_path)


def test_a_recorded_trace_loads(tmp_path):
    """A profiler trace recorded on a TPU v5e: three ticks of a one-slot
    server at the registered scene shape, each in a bench.tick span."""
    ops, spans = trace.load(_unpack(tmp_path, "v5e_ticks.xplane.pb.gz"))
    assert ops and all(evs for evs in ops.values())
    ticks = [s for s in spans if s[0] == "bench.tick"]
    assert len(ticks) >= 3
    lo, hi = ticks[0][1], ticks[-1][2]
    r = trace.reduce(ops, spans, lo, hi)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert any("tpu_custom_call" in n for n in r["op_seconds"])


def test_the_recorded_trace_carries_scopes():
    """The older recording's tick ops carry their scope path; an op named
    after an argument (``cache['k']:``) carries none."""
    import gzip

    with gzip.open(os.path.join(DATA, "v5e_ticks.xplane.pb.gz")) as f:
        tf_ops = trace.op_tf_ops(f.read())
    paths = [trace.scope_path(t) for evs in tf_ops.values() for _, t in evs
             if t]
    scoped = [p for p in paths if p]
    assert len(scoped) > 300
    assert all(p[:2] == ("jit(_tick_impl)", "sim_server.tick")
               for p in scoped)
    assert trace.scope_path("cache['k']:") == ()


def test_scopes_and_program_spans_of_a_recorded_serve_trace(tmp_path):
    """A profiler trace recorded on a TPU v5e at the scene shape of the
    cells: ticks of a one-slot sim-se2-fourier server, one admitting a
    lane, each in a bench.tick span (``record_serve_trace.py``)."""
    ops, spans = trace.load(
        _unpack(tmp_path, "v5e_serve.xplane.pb.gz"), ("sim_server.",))
    names = {n for n, _, _ in spans}
    assert {"bench.tick", "sim_server.tick", "sim_server.drain",
            "sim_server.admit"} <= names
    ticks = [s for s in spans if s[0] == "bench.tick"]
    r = trace.reduce(ops, spans, ticks[0][1], ticks[-1][2])
    sec = r["scope_seconds"]
    for scope in ("agent_sim.cache_write", "agent_sim.se2_transform",
                  "agent_sim.decode_attention", "sim_server.tick"):
        assert sec.get(scope, 0) > 0, (scope, sorted(sec))
    # an op counts once under each scope it sits in, never twice
    assert max(sec.values()) <= sum(r["op_seconds"].values()) + 1e-12
    assert {n for n, _, _ in r["spans"]} == names
    # without the runner's prefixes only the benchmark's spans are kept
    _, own = trace.load(str(tmp_path))
    assert {n for n, _, _ in own} == {n for n in names
                                      if n.startswith("bench.")}


SCOPES = {"fusion.1": ("jit(tick)", "model", "model.attn"),
          "_decode_kernel": ("jit(tick)", "model", "model.attn"),
          "copy": ("jit(tick)", "model")}
SCOPED = [op + (SCOPES[op[0]],) for op in OPS]


def test_scope_path_drops_the_primitive_and_merges_fused_names():
    assert trace.scope_path("jit(f)/a/b/dot_general:") == ("jit(f)", "a",
                                                           "b")
    assert trace.scope_path("jit(f)/a/x;jit(f)/c/y:") == ("jit(f)", "a",
                                                          "c")
    assert trace.scope_path("add:") == ()
    assert trace.scope_path("") == ()


def test_scope_seconds_count_nested_scopes_and_ops_without_one():
    ops = SCOPED + [("unnamed", 8.0, 9.0, ()), ("bare", 9.0, 9.5)]
    got = dict(trace.time_by_scope(ops))
    assert got["model.attn"] == pytest.approx(2.0 + 1.5)
    assert got["model"] == pytest.approx(2.0 + 1.5 + 0.5)
    assert got["jit(tick)"] == got["model"]
    assert got[trace.NO_SCOPE] == pytest.approx(1.5)


def test_reduce_clips_scopes_and_spans_to_the_window():
    spans = SPANS + [("sim_server.tick", 0.5, 2.5)]
    r = trace.reduce({"/device:TPU:0": SCOPED}, spans, 1.0, 6.0)
    # inside the window: the kernel [1, 2] and fusion.1 [4, 5]
    assert r["scope_seconds"]["model.attn"] == pytest.approx(1.0 + 1.0)
    assert "copy" not in r["op_seconds"]
    assert r["devices"] == 1
    assert r["spans"] == [("bench.window", 1.0, 6.0),
                          ("bench.tick", 1.0, 3.0),
                          ("sim_server.tick", 1.0, 2.5),
                          ("bench.tick", 3.5, 6.0),
                          ("bench.submit", 5.2, 6.0)]
    # gap [2, 4], mid 3.0: both ticks have ended, so the window; gap
    # [5, 6], mid 5.5: the submit
    gaps = dict(r["idle_gaps"])
    assert gaps == pytest.approx({"bench.window": 2.0, "bench.submit": 1.0})


def test_idle_gaps_charge_the_innermost_program_span():
    spans = SPANS + [("sim_server.tick", 3.6, 7.9),
                     ("sim_server.drain", 5.5, 7.0)]
    gaps = dict(trace.idle_gaps(OPS, spans, 0.0, 10.0))
    # [5, 7] mid 6.0: bench.tick, sim_server.tick and its drain open; the
    # drain started last
    assert gaps["sim_server.drain"] == pytest.approx(2.0)
    assert "bench.tick" not in gaps
    assert sum(gaps.values()) == pytest.approx(10.0 - 3.5)


def _pb(*fields):
    """A protobuf message from (field number, value) pairs: an int as a
    varint, a str or bytes as a length-delimited field."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n >> 7 else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_ops_of_one_name_in_two_programs_keep_their_own_scopes(tmp_path):
    """Two programs hold the same HLO line, so two event metadata entries
    share one name: each event is charged to the scope of the metadata it
    points to, not to whichever entry came last."""
    op = "%copy.1 = f32[8]{0} copy(f32[8]{0} %p.1)"

    def meta(i, tf_op):      # map entry: XEventMetadata with a tf_op stat
        return _pb((1, i), (2, _pb((1, i), (2, op),
                                   (5, _pb((1, 7), (5, tf_op))))))

    def event(mid, start_ps, dur_ps):
        return _pb((1, mid), (2, start_ps), (3, dur_ps))

    line = _pb((1, 1), (2, trace.OPS_LINE), (3, 0),
               (4, event(1, 0, 10**12)), (4, event(2, 2 * 10**12, 10**12 // 2)),
               (4, event(1, 3 * 10**12, 10**12 // 4)))
    plane = _pb((1, 1), (2, "/device:TPU:0"), (3, line),
                (4, meta(1, "jit(tick)/agent_sim.cache_write/copy:")),
                (4, meta(2, "jit(admit)/sim_server.admit/copy:")),
                (5, _pb((1, 7), (2, _pb((1, 7), (2, trace.SCOPE_STAT))))))
    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    (prof / "two.xplane.pb").write_bytes(_pb((1, plane)))
    ops, spans = trace.load(str(tmp_path))
    tick = ("jit(tick)", "agent_sim.cache_write")
    admit = ("jit(admit)", "sim_server.admit")
    assert [(n, s, e, sc) for n, s, e, sc in ops["/device:TPU:0"]] == [
        (op, 0.0, pytest.approx(1.0), tick),
        (op, pytest.approx(2.0), pytest.approx(2.5), admit),
        (op, pytest.approx(3.0), pytest.approx(3.25), tick)]
    assert spans == []
    r = trace.reduce(ops, spans, 0.0, 4.0)
    assert r["op_seconds"] == {op: pytest.approx(1.75)}
    assert r["scope_seconds"]["agent_sim.cache_write"] == pytest.approx(1.25)
    assert r["scope_seconds"]["sim_server.admit"] == pytest.approx(0.5)


def test_load_keeps_the_runner_spans_and_drops_the_rest(tmp_path):
    """A trace recorded here (no device plane): the host spans ``load``
    keeps are the benchmark's and the declared prefixes'."""
    import jax

    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for name in ("toy.step", "toy.step.sample", "other.step"):
                with jax.profiler.TraceAnnotation(name):
                    jax.block_until_ready(jax.numpy.ones(3) + 1)
    ops, spans = trace.load(str(tmp_path), ("toy.",))
    assert sorted(n for n, _, _ in spans) == ["bench.window", "toy.step",
                                              "toy.step.sample"]
    assert all(s <= e for _, s, e in spans)
    assert not ops


# -- work counts ------------------------------------------------------------------

def test_row_widths_and_bytes():
    assert work.row_widths(SE2) == (200, 200)
    assert work.row_widths(ABS) == (24, 24)
    # 6 layers x 8 heads x (200 + 200) floats
    assert work.kv_row_bytes(SE2, "float32") == 6 * 8 * 400 * 4
    assert work.kv_row_bytes(SE2, "int8") == 6 * 8 * (400 + 8)


def test_dense_flops_per_token():
    # per layer: q, k, v, o (4 x 256 x 192) and SwiGLU (3 x 256 x 1024)
    per_layer = 2 * (4 * 256 * 192 + 3 * 256 * 1024)
    assert work.dense_flops_per_token(SE2) == 6 * per_layer + 2 * 256 * 63


def test_decode_work_of_a_tick():
    flops, nbytes = work.decode_work(SE2, "float32", 2, [10, 20])
    assert nbytes == 30 * 6 * 8 * 400 * 4
    assert flops == 2 * 30 * 6 * 8 * 2 * 400


def test_least_time_names_its_bound():
    p = peaks.peaks("TPU v5 lite")
    t, bound = work.least_time(197e12, 1.0, p)
    assert (t, bound) == (1.0, "compute")
    t, bound = work.least_time(1.0, 819e9, p)
    assert (t, bound) == (1.0, "memory")


# -- the control's unicycle step ---------------------------------------------------

GRID = {"accel_bins": 7, "yaw_bins": 9, "max_accel": 3.0,
        "max_yaw_rate": 0.5, "dt": 0.5, "max_speed": 25.0}


def test_the_bfloat16_step_keeps_the_float32_pose():
    """Far from the origin a standing agent stays exactly where it is: the
    control rounds the step's arithmetic, not the world coordinates."""
    from bench import check, reference

    pose = np.array([[1234.567, -987.654, 3.3]], np.float32)
    still = 3 * 9 + 4                         # zero accel, zero yaw rate
    args = (pose, np.zeros(1, np.float32), np.array([still]),
            np.array([True]))
    np.testing.assert_array_equal(check.kinematics_bf16(GRID, *args)[0],
                                  pose)
    moving = (pose, np.full(1, 20.0, np.float32), np.array([still + 2]),
              np.array([True]))
    exact = reference.kinematics(GRID, *moving)[0]
    low = check.kinematics_bf16(GRID, *moving)[0]
    assert 1e-4 < np.abs(low - exact).max() < 0.5


# -- peaks ---------------------------------------------------------------------------

def test_unknown_device_kind_raises():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12


# -- the command ---------------------------------------------------------------------

def test_run_refuses_a_machine_without_a_tpu():
    import subprocess

    root = os.path.dirname(os.path.dirname(DATA))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(root, "run.py"), "--workload",
         "se2-wosac-serve", "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_short_names_group_ops_by_instruction_and_kind():
    ops = {
        "%copy.245 = f32[6,6]{1,0:T(8,128)} copy(f32[6,6]{0,1} %cache__k__)":
            1.0,
        "%copy.258 = f32[6,6]{1,0:T(8,128)} copy(f32[6,6]{0,1} %cache__v__)":
            2.0,
        '%sim_server.tick.11 = (f32[2]{0:T(128)}, f32[2]{0}) custom-call('
        's32[2]{0} %x), custom_call_target="tpu_custom_call"': 0.5,
    }
    assert trace.grouped(ops) == [("copy (copy)", 3.0),
                                  ("sim_server.tick (tpu_custom_call)", 0.5)]
