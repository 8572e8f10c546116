"""A runner that is not the agent-sim server, on a configuration with none
of the agent-sim keys (no ``model``, ``action_grid`` or ``cache_dtype``),
goes through ``run.execute`` and ``run.result`` to a result line: the path
a new model configuration takes, with new files only."""
import json
import os
import sys
import types

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench import run, trace  # noqa: E402

KIND = "toy_lm"
CELL = "toy-lm-serve"
#: a catalog model's sizes sit at the top level of its configuration file
CONFIG = {"name": "toy-lm", "hidden_size": 64, "num_hidden_layers": 2,
          "vocab_size": 128}
BENCH = {
    "end_to_end": [
        {"name": "rollout_steps_per_s", "unit": "steps/s",
         "workloads": ["se2-wosac-serve", CELL]},
        {"name": "step_gap_p95_ms", "unit": "ms", "workloads": [CELL]},
        {"name": "setup_s", "unit": "s"}],
    "per_layer": [
        {"name": "cache_write_share.serve", "unit": "%",
         "workloads": [CELL]},
        {"name": "se2_transform_share.serve", "unit": "%",
         "workloads": [CELL]},
        {"name": "tick_host_ms.serve", "unit": "ms", "workloads": [CELL]},
        {"name": "device_idle_share.serve", "unit": "%"},
        {"name": "decode_roofline", "unit": "%",
         "workloads": ["se2-wosac-serve"]}],
}
DEVICE = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")


def _toy_run(cell):
    """The runner contract, on a model the harness knows nothing of."""
    assert (cell.model, cell.grid, cell.cache_dtype) == (None, None, None)
    with cell.profile() as prof:
        prof.mark_start()
        steps = cell.config["num_hidden_layers"] * 10
        prof.mark_end()
    return {"setup_s": 1.5,
            "metrics": {"rollout_steps_per_s": float(steps),
                        "step_gap_p95_ms": 12.5},
            "layer": {},
            "readings": {"logit_gap": 0.01, "tokens_compared": 64},
            "control": None, "attempted": 4, "failed": 0,
            "memory_peak_bytes": cell.memory_peak(),
            "info": {"steps": steps}}


@pytest.fixture
def toy_runner(monkeypatch):
    mod = types.ModuleType("bench." + KIND)
    mod.run = _toy_run
    mod.SPANS = ("toy_server.",)
    monkeypatch.setitem(sys.modules, "bench." + KIND, mod)
    return mod


def _cell(trace_on=False, kind=KIND):
    return run.Cell(CELL, dict(CONFIG), {"kind": kind, "requests": 4},
                    {"logit_gap": 0.1}, seed=2 ** 31 + 5, seconds=1.0,
                    trace=trace_on)


def test_a_new_runner_and_configuration_print_a_result_line(toy_runner,
                                                            capsys):
    cell = _cell()
    assert run.runner(cell) is toy_runner
    outcome = run.execute(cell)
    out, lines = run.result(cell, outcome, BENCH, DEVICE)
    print(json.dumps(out))
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["correct"] is True
    assert (last["attempted"], last["failed"]) == (4, 0)
    assert last["metrics"] == {
        "rollout_steps_per_s": {"value": 20.0, "unit": "steps/s"},
        "step_gap_p95_ms": {"value": 12.5, "unit": "ms"},
        "setup_s": {"value": 1.5, "unit": "s"}}
    assert last["device"] == {"platform": "tpu", "kind": "TPU v5 lite",
                              "count": 1, "memory_peak_bytes": 0}
    assert list(last)[-1] == "checks"
    assert last["checks"] == {"logit_gap": {"value": 0.01, "limit": 0.1}}
    assert lines == ["check logit_gap: 0.01 (limit 0.1)"]


def test_a_traced_new_cell_reads_its_scopes_and_spans(toy_runner):
    """The readers find the new runner's spans and the model's scopes in
    the reduction, and leave out a metric whose scope is absent."""
    cell = _cell(trace_on=True)
    outcome = _toy_run(_cell())
    ops = {"/device:TPU:0": [
        ("fusion.3", 0.0, 1.0, ("jit(step)", "lm.mlp")),
        ("dus.1", 1.0, 1.6, ("jit(step)", "agent_sim.cache_write")),
        ("kernel", 2.0, 3.5, ("jit(step)", "lm.attention"))]}
    spans = [("bench.window", 0.0, 4.0),
             ("sim_server.tick", 0.0, 1.0), ("sim_server.drain", 0.6, 1.0),
             ("sim_server.tick", 2.0, 3.0), ("sim_server.drain", 2.1, 2.8),
             ("sim_server.tick", 3.0, 3.9), ("sim_server.drain", 3.1, 3.2)]
    cell.trace_result = trace.reduce(ops, spans, 0.0, 4.0)
    out, _ = run.result(cell, outcome, BENCH, DEVICE)
    m = {k: v["value"] for k, v in out["metrics"].items()}
    # 0.6 s of writes over 3.1 busy seconds
    assert m["cache_write_share.serve"] == pytest.approx(100 * 0.6 / 3.1)
    # tick less drain: 0.6, 0.3, 0.8 s -> median 0.6 s
    assert m["tick_host_ms.serve"] == pytest.approx(600.0)
    assert m["device_idle_share.serve"] == pytest.approx(22.5)
    assert "se2_transform_share.serve" not in m
    assert "decode_roofline" not in m
    assert out["device"]["busy_s"] == pytest.approx(3.1)
    # gap [3.5, 4.0] lies in the last tick, gap [1.6, 2.0] between ticks
    assert out["breakdown"]["idle_gaps"] == [
        ["sim_server.tick", pytest.approx(0.5)],
        ["bench.window", pytest.approx(0.4)]]


def test_an_unknown_kind_names_the_missing_module():
    with pytest.raises(SystemExit, match=r"bench\.no_such_kind"):
        run.execute(_cell(kind="no_such_kind"))


@pytest.mark.parametrize("kind", ["../serve", "serve.run", "", 3])
def test_a_kind_that_is_no_plain_identifier_is_refused(kind):
    with pytest.raises(SystemExit, match="plain identifier"):
        run.runner(_cell(kind=kind))


def test_the_serving_runner_declares_the_server_spans():
    cell = run.Cell.from_benchmark("se2-wosac-serve", seed=1, seconds=1.0,
                                   trace=False)
    assert run.runner(cell).SPANS == ("sim_server.",)
