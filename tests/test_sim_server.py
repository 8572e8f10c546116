"""Isolation & invariance suite for the continuous-batching sim server.

The contract under test: a scene served by a churning ``SimServer`` —
recycled slots, co-resident strangers, adversarially scribbled stale
cache rows, arbitrary arrival schedules — produces **bit-identical**
per-step actions, poses, and metrics to the same scene run alone in a
fresh ``RolloutEngine``. Not "close": identical. Anything weaker would
mean slot state leaks across admissions.
"""
import numpy as np
import pytest

import jax

from repro.nn import module as nnm
from repro.nn.agent_sim import AgentSimConfig, AgentSimModel
from repro.runtime.evaluation import METRICS, EvalConfig, scene_metrics
from repro.runtime.rollout import RolloutEngine
from repro.runtime.sim_server import (SceneRequest, SimServer, poisson_drive,
                                      serve_scenes)
from repro.scenarios import ScenarioConfig
from repro.scenarios.registry import generate_mixed, generate_scene

from serving_utils import assert_bit_identical, scribble_stale_rows

SCEN = ScenarioConfig(num_map=8, num_agents=3, num_steps=6)
T_HIST = 3
MATRIX = [("float32", "xla"), ("float32", "ref"),
          ("int8", "xla"), ("int8", "ref")]


def _model(seed=0):
    cfg = AgentSimConfig(d_model=32, num_layers=2, num_heads=2, head_dim=12,
                         d_ff=64, num_actions=SCEN.num_actions,
                         encoding="se2_fourier", attn_impl="ref")
    model = AgentSimModel(cfg)
    return model, nnm.init_params(model.specs(), jax.random.key(seed))


MODEL, PARAMS = _model()


def _solo_reference(scene, cache_dtype, impl, seed=9):
    """The scene run alone, fresh engine, one slot: the ground truth every
    server schedule must reproduce bit-for-bit."""
    eng = RolloutEngine(MODEL, PARAMS, SCEN, num_slots=1,
                        cache_dtype=cache_dtype, decode_impl=impl)
    fut = eng.run([scene], t_hist=T_HIST, n_samples=1, seed=seed)
    return fut[0, 0], eng.last_actions[0, 0]      # (Tf, A, 3), (Tf, A)


@pytest.mark.parametrize("cache_dtype,impl", MATRIX,
                         ids=[f"{d}-{i}" for d, i in MATRIX])
def test_recycled_slot_bit_identical_to_solo(cache_dtype, impl):
    """The full churn gauntlet, one pass per {dtype} x {decode impl}:

    1. fill both slots with evictee scenes of different families and a
       *different* (shorter) horizon;
    2. evict one MID-PREFILL, let the other retire at its horizon;
    3. scribble every stale row of the shared slab with garbage;
    4. admit the victim into a recycled slot alongside fresh noisy
       neighbors and demand bit-identical actions, poses, and metrics
       vs the fresh solo engine."""
    victim = generate_scene("signalized_intersection", 40, 0, SCEN)
    ref_fut, ref_acts = _solo_reference(victim, cache_dtype, impl)

    srv = SimServer(MODEL, PARAMS, SCEN, num_slots=2,
                    cache_dtype=cache_dtype, decode_impl=impl)
    evictees = generate_mixed(7, 100, 2, SCEN)
    srv.submit(SceneRequest(uid=100, tensors=evictees[0], t_hist=2,
                            t_total=4, seed=1, scene_id=50))
    srv.submit(SceneRequest(uid=101, tensors=evictees[1], t_hist=2,
                            t_total=4, seed=1, scene_id=51))
    srv.tick()                                    # both slots mid-prefill
    assert srv.evict(101)                         # mid-prefill eviction
    for _ in range(4):                            # uid=100 retires (t_total)
        srv.tick()
    assert all(s.req is None for s in srv.slots)
    assert srv.admitted == 2 and srv.evicted == 1

    # every slot cursor is stale now: poison the whole slab beyond 0
    srv.flush()
    srv.cache = scribble_stale_rows(
        srv.cache, np.zeros(2, np.int32), srv.max_len, seed=3)

    # victim + a noisy neighbor into the recycled slots
    srv.submit(SceneRequest(uid=0, tensors=victim, t_hist=T_HIST,
                            seed=9, scene_id=0, sample_id=0))
    srv.submit(SceneRequest(uid=1, tensors=evictees[0], t_hist=2,
                            seed=2, scene_id=77))
    done = srv.run_until_drained()
    assert sorted(done) == [0, 1, 100]    # 100 finished pre-churn; 101 evicted

    assert_bit_identical(done[0].actions, ref_acts,
                         f"actions ({cache_dtype}/{impl})")
    assert_bit_identical(done[0].future, ref_fut,
                         f"poses ({cache_dtype}/{impl})")
    ecfg = EvalConfig(t_hist=T_HIST, n_samples=1)
    m_ref = scene_metrics(SCEN, ecfg, victim, ref_fut[None])
    m_srv = scene_metrics(SCEN, ecfg, victim, done[0].future[None])
    for k in METRICS:
        assert (m_srv[k] == m_ref[k]
                or (np.isnan(m_srv[k]) and np.isnan(m_ref[k]))), \
            (k, m_srv[k], m_ref[k])


def test_mid_prefill_eviction_frees_slot_for_identical_successor():
    """A successor admitted into a slot whose predecessor died mid-prefill
    must match the fresh solo run — the half-written prefill rows are
    beyond the reset cursor and unreachable."""
    victim = generate_scene("onramp_merge", 41, 0, SCEN)
    ref_fut, ref_acts = _solo_reference(victim, "float32", "ref")

    srv = SimServer(MODEL, PARAMS, SCEN, num_slots=1,
                    cache_dtype="float32", decode_impl="ref")
    srv.submit(SceneRequest(uid=5, tensors=generate_scene("highway", 1, 0,
                                                          SCEN),
                            t_hist=4, seed=3, scene_id=5))
    srv.tick(); srv.tick()                        # 2 of 4 prefill ticks in
    assert srv.slots[0].req.uid == 5
    assert srv.evict(5)
    srv.submit(SceneRequest(uid=0, tensors=victim, t_hist=T_HIST,
                            seed=9, scene_id=0))
    done = srv.run_until_drained()
    assert sorted(done) == [0]
    assert_bit_identical(done[0].actions, ref_acts, "actions after evict")
    assert_bit_identical(done[0].future, ref_fut, "poses after evict")


def test_retrace_guard_one_compile_across_recycle_generations():
    """Admit/evict churn over >= 3 full slot-recycle generations must hit
    the jit cache every time: exactly one tick trace, one admit trace.
    A shape leaking into the hot loop (host int vs traced value, dtype
    drift on recycled state) fails here instead of silently recompiling."""
    srv = SimServer(MODEL, PARAMS, SCEN, num_slots=2, cache_dtype="float32",
                    decode_impl="ref")
    scenes = generate_mixed(5, 0, 8, SCEN)        # 8 scenes / 2 slots = 4 gens
    for i, s in enumerate(scenes):
        srv.submit(SceneRequest(uid=i, tensors=s, t_hist=2 + (i % 3),
                                t_total=4 + (i % 3), seed=i, scene_id=i))
    # sprinkle evictions into the churn as well
    ticks = 0
    while srv.queue or any(s.req for s in srv.slots):
        srv.tick()
        ticks += 1
        if ticks == 3:
            assert srv.evict(srv.slots[0].req.uid)
    srv.flush()
    assert srv.admitted == 8 and srv.evicted == 1
    assert srv.tick_traces == 1, "tick recompiled under churn"
    assert srv.admit_traces == 1, "admission recompiled under churn"


def test_serve_scenes_matches_engine_batch():
    """Engine-shaped entry: futures bit-match RolloutEngine.run across the
    whole (scene, sample) grid even when slots << lanes."""
    scenes = generate_mixed(11, 0, 3, SCEN)
    eng = RolloutEngine(MODEL, PARAMS, SCEN, num_slots=3,
                        cache_dtype="float32", decode_impl="ref")
    ref = eng.run(scenes, t_hist=T_HIST, n_samples=2, seed=13)
    srv = SimServer(MODEL, PARAMS, SCEN, num_slots=2,
                    cache_dtype="float32", decode_impl="ref")
    got = serve_scenes(srv, scenes, t_hist=T_HIST, n_samples=2, seed=13)
    assert_bit_identical(got, ref, "serve_scenes futures")


# -- schedule invariance ------------------------------------------------------

N_PROP_SCENES = 3


def _scene_set():
    return generate_mixed(21, 0, N_PROP_SCENES, SCEN)


def _per_scene_metrics(scenes, futures_by_sid):
    ecfg = EvalConfig(t_hist=T_HIST, n_samples=1)
    return [scene_metrics(SCEN, ecfg, s, futures_by_sid[i][None])
            for i, s in enumerate(scenes)]


def _check_schedule_invariant(order_seed, rate, num_slots):
    """Any admission schedule of the same scene set — permuted arrival
    order, Poisson gaps, any slot count — yields the same per-scene
    futures and therefore the same per-scene metrics, bit-for-bit."""
    scenes = _scene_set()
    eng = RolloutEngine(MODEL, PARAMS, SCEN, num_slots=2,
                        cache_dtype="float32", decode_impl="ref")
    ref = eng.run(scenes, t_hist=T_HIST, n_samples=1, seed=17)
    ref_by_sid = {i: ref[i, 0] for i in range(len(scenes))}

    order = np.random.default_rng(order_seed).permutation(len(scenes))
    srv = SimServer(MODEL, PARAMS, SCEN, num_slots=num_slots,
                    cache_dtype="float32", decode_impl="ref")
    reqs = [SceneRequest(uid=int(sid), tensors=scenes[sid], t_hist=T_HIST,
                         seed=17, scene_id=int(sid)) for sid in order]
    poisson_drive(srv, reqs, rate=rate, seed=order_seed)
    assert sorted(srv.done) == list(range(len(scenes)))
    got_by_sid = {sid: srv.done[sid].future for sid in srv.done}
    for sid in ref_by_sid:
        assert_bit_identical(
            got_by_sid[sid], ref_by_sid[sid],
            f"scene {sid} under schedule (order_seed={order_seed}, "
            f"rate={rate}, slots={num_slots})")
    for m_ref, m_got in zip(_per_scene_metrics(scenes, ref_by_sid),
                            _per_scene_metrics(scenes, got_by_sid)):
        for k in METRICS:
            assert (m_got[k] == m_ref[k]
                    or (np.isnan(m_got[k]) and np.isnan(m_ref[k]))), \
                (k, m_got[k], m_ref[k])


try:
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=4, deadline=None, derandomize=True)
    @given(order_seed=st.integers(0, 2 ** 16),
           rate=st.floats(float(np.float32(0.2)), 3.0, allow_nan=False,
                          width=32),
           num_slots=st.integers(1, 3))
    def test_metrics_invariant_to_arrival_schedule(order_seed, rate,
                                                   num_slots):
        _check_schedule_invariant(order_seed, rate, num_slots)

except ImportError:            # hypothesis is an optional dev dep:
    @pytest.mark.parametrize(  # fall back to fixed schedules
        "order_seed,rate,num_slots",
        [(0, 1.0, 2), (7, 0.3, 1), (123, 2.5, 3)])
    def test_metrics_invariant_to_arrival_schedule(order_seed, rate,
                                                   num_slots):
        _check_schedule_invariant(order_seed, rate, num_slots)
