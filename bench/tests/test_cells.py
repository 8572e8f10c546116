"""Each cell end to end on the CPU at a tiny size, with the chip check
skipped: a sound run is correct, a run whose timed path is broken
underneath comes out not correct, once for each fault the cell can have,
and so does the control (the reference with float8-rounded matmul operands
in the model's place), by the harness's own verdict."""
import contextlib

import pytest

from tiny import readings, tiny_cell

from bench import run, serve

SERVE = ["se2-wosac-serve", "absolute-wosac-serve", "open"]


@pytest.mark.parametrize("workload", SERVE)
def test_a_sound_run_is_correct(workload):
    cell = tiny_cell(workload)
    out = readings(cell)
    assert out["attempted"] > 0 and out["failed"] == 0
    assert run.correct_of(out["readings"], cell.limits), out["readings"]
    assert all(v > 0 for v in out["metrics"].values())


@contextlib.contextmanager
def patched(obj, name, wrap):
    orig = getattr(obj, name)
    setattr(obj, name, wrap(orig))
    try:
        yield
    finally:
        setattr(obj, name, orig)


def _unchanged_cache(orig):
    """The tick's model step writes nothing: the cache comes back as it
    went in."""
    def step(self, params, cache, *a, **kw):
        logits, _ = orig(self, params, cache, *a, **kw)
        return logits, cache
    return step


def _altered_action(orig):
    """One served action id is changed where the tick produces it."""
    def body(self, *a):
        cache, state, acts, pose = orig(self, *a)
        return cache, state, acts.at[0, 0].add(1) % 63, pose
    return body


@pytest.mark.parametrize("workload", SERVE)
@pytest.mark.parametrize("fault", ["unchanged_state", "altered_token"])
def test_a_broken_serving_path_is_not_correct(workload, fault):
    from repro.nn.agent_sim import AgentSimModel
    from repro.runtime.sim_server import SimServer

    target = {"unchanged_state": (AgentSimModel, "step", _unchanged_cache),
              "altered_token": (SimServer, "_tick_body", _altered_action)}
    with patched(*target[fault]):
        cell = tiny_cell(workload)
        out = readings(cell)
    assert not run.correct_of(out["readings"], cell.limits), out["readings"]


@pytest.mark.parametrize("workload", ["se2-wosac-serve",
                                      "absolute-wosac-serve"])
def test_the_control_is_not_correct(workload):
    cell = tiny_cell(workload, control=True, check_lanes=8, num_steps=24)
    out = readings(cell)
    assert run.correct_of(out["readings"], cell.limits), out["readings"]
    assert not run.correct_of(out["control"], cell.limits), out["control"]
    for k in cell.limits:
        assert out["control"][k] > out["readings"][k], (k, out["control"])


def test_the_stagger_spreads_the_slots_over_a_lane():
    """After warm-up each slot of a staggered saturated cell is at its own
    step of its lane, and the window admits as it runs."""
    cell = tiny_cell("se2-wosac-serve", slots=4, num_steps=12)
    srv, _, pool = serve.build(cell)
    lanes = serve.Lanes(srv)
    traffic = serve.Traffic(cell, srv, lanes, pool)
    traffic.prime(0.0)
    serve.drive(cell, srv, traffic, lanes, until=lambda now: True,
                annotate=False)
    serve.drive(cell, srv, traffic, lanes, annotate=False,
                until=lambda now: traffic.steady())
    steps = sorted(s.t for s in srv.slots)
    assert len(set(steps)) == 4, steps
    assert srv.admitted == 5
    ticks0 = srv.ticks
    serve.drive(cell, srv, traffic, lanes, annotate=False,
                until=lambda now: srv.ticks - ticks0 >= 12)
    assert srv.admitted == 9


def test_the_window_waits_for_every_tick_dispatched_ahead():
    """With ticks dispatched ahead of the drain, closing the window lands
    every outstanding tick's actions, one tick at a time, so no lane's
    actions arrive in a lump."""
    cell = tiny_cell("se2-wosac-serve", slots=3, num_steps=12)
    cell.config["drain_lag"] = 6
    srv, _, pool = serve.build(cell)
    assert srv.drain_lag == 6
    lanes = serve.Lanes(srv)
    traffic = serve.Traffic(cell, srv, lanes, pool)
    traffic.prime(0.0)
    serve.drive(cell, srv, traffic, lanes, annotate=False,
                until=lambda now: srv.ticks >= 15)
    assert len(srv._pending) == 6
    landed = len(lanes.arrived)
    t = serve.settle(srv, lanes)
    assert not srv._pending
    new = lanes.arrived[landed:]
    assert len(new) >= 6 and all(n == 1 and s <= t for s, n in new), new
