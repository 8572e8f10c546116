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


def test_a_recorded_trace_loads(tmp_path):
    """A profiler trace recorded on a TPU v5e: three ticks of a one-slot
    server at the registered scene shape, each in a bench.tick span."""
    import gzip
    import shutil

    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    with gzip.open(os.path.join(DATA, "v5e_ticks.xplane.pb.gz")) as src, \
            open(prof / "ticks.xplane.pb", "wb") as dst:
        shutil.copyfileobj(src, dst)
    ops, spans = trace.load(str(tmp_path))
    assert ops and all(evs for evs in ops.values())
    ticks = [s for s in spans if s[0] == "bench.tick"]
    assert len(ticks) >= 3
    lo, hi = ticks[0][1], ticks[-1][2]
    r = trace.reduce(ops, spans, lo, hi)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert any("tpu_custom_call" in n for n in r["op_seconds"])


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
