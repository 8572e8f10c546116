"""Scenario-suite tests: registry, determinism, lane-graph topology,
variable-agent-count masking, mask-aware metrics, and the end-to-end
SE(2) property — globally re-posing any family's scene leaves closed-loop
evaluation metrics unchanged for relative encodings.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios
from repro.nn import module as nnm
from repro.nn.agent_sim import AgentSimConfig, AgentSimModel
from repro.runtime.evaluation import EvalConfig, evaluate_scenes
from repro.runtime.rollout import RolloutEngine
from repro.scenarios import registry
from repro.scenarios.lane_graph import STEP

jax.config.update("jax_enable_x64", False)

CFG = scenarios.ScenarioConfig(num_map=16, num_agents=6, num_steps=10)
FAMILIES = registry.names()

TENSOR_KEYS = {"map_feats", "map_pose", "map_valid", "agent_feats",
               "agent_pose", "agent_valid", "actions", "behavior",
               "agent_type"}


# ---------------------------------------------------------------------------
# registry + determinism
# ---------------------------------------------------------------------------

def test_registry_discoverable():
    assert len(FAMILIES) >= 6
    for expected in ("freeform", "highway", "onramp_merge", "roundabout",
                     "signalized_intersection", "unprotected_left",
                     "pedestrian_crossing"):
        assert expected in FAMILIES
    with pytest.raises(KeyError):
        registry.get("no_such_family")


@pytest.mark.parametrize("family", FAMILIES)
def test_family_deterministic_from_cursor(family):
    a = registry.generate_scene(family, seed=3, index=11, cfg=CFG)
    b = registry.generate_scene(family, seed=3, index=11, cfg=CFG)
    assert set(a.tensors) == TENSOR_KEYS
    for k in a.tensors:
        np.testing.assert_array_equal(a.tensors[k], b.tensors[k],
                                      err_msg=f"{family}/{k}")
    c = registry.generate_scene(family, seed=3, index=12, cfg=CFG)
    assert any(not np.array_equal(a.tensors[k], c.tensors[k])
               for k in ("agent_pose", "map_pose")), \
        f"{family}: index does not vary the scene"


@pytest.mark.parametrize("family", FAMILIES)
def test_scene_shapes_and_masks(family):
    s = registry.generate_scene(family, seed=0, index=2, cfg=CFG)
    t, a, m = CFG.num_steps, CFG.num_agents, CFG.num_map
    tt = s.tensors
    assert tt["map_pose"].shape == (m, 3)
    assert tt["map_feats"].shape == (m, CFG.map_feat_dim)
    assert tt["agent_pose"].shape == (t, a, 3)
    assert tt["agent_feats"].shape == (t, a, CFG.agent_feat_dim)
    assert tt["agent_valid"].shape == (t, a)
    assert tt["actions"].shape == (t, a)
    assert tt["actions"].min() >= 0
    assert tt["actions"].max() < CFG.num_actions
    # valid-first packing, constant over time
    valid0 = tt["agent_valid"][0]
    n = int(valid0.sum())
    assert 1 <= n <= a
    assert valid0[:n].all() and not valid0[n:].any()
    np.testing.assert_array_equal(
        tt["agent_valid"], np.broadcast_to(valid0, (t, a)))
    # behavior labels only for valid agents
    assert (tt["behavior"][:n] >= 0).all()
    assert (tt["behavior"][n:] == -1).all() or n == a
    # speed feature convention: channel 0 is speed/10, consistent with the
    # pose deltas the rollout engine integrates
    assert tt["agent_feats"][..., 0].min() >= 0.0


def test_agent_counts_vary_across_indices():
    counts = {
        fam: {registry.generate_scene(fam, 0, i, CFG).num_valid_agents
              for i in range(8)}
        for fam in FAMILIES if fam != "freeform"}
    assert any(len(v) > 1 for v in counts.values()), counts


# ---------------------------------------------------------------------------
# lane-graph topology invariants
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("family", FAMILIES)
def test_lane_graph_topology(family):
    s = registry.generate_scene(family, seed=1, index=0, cfg=CFG)
    g = s.lane_graph
    assert g is not None and len(g.lanes) >= 1
    for a, succs in enumerate(g.successors):
        for b in succs:
            end, start = g.lanes[a].points[-1], g.lanes[b].points[0]
            gap = np.linalg.norm(end - start)
            assert gap <= STEP, \
                f"{family}: lane {a}->{b} endpoint gap {gap:.2f}m"
    # centerline points are on-road; a point far outside is not
    pts, _ = g.all_points()
    assert g.on_road(pts[:: max(1, len(pts) // 16)]).all()
    far = pts.max(axis=0) + 500.0
    assert not g.on_road(far).any()
    # route tracing follows successors and only ever extends the route
    rng = np.random.default_rng(0)
    route = g.trace_route(0, 100.0, rng)
    assert route[0] == 0
    for a, b in zip(route, route[1:]):
        assert b in g.successors[a]
    xy, hd = g.route_points(route)
    assert xy.shape[0] == hd.shape[0] >= len(g.lanes[0].points)


def test_map_tokens_cover_every_lane():
    """Token budget >= lane count => every lane owns at least one map
    token — its first centerline point is always sampled (left-turn arcs
    etc. must never be invisible to the model)."""
    s = registry.generate_scene("signalized_intersection", 0, 0, cfg=CFG)
    g = s.lane_graph
    assert CFG.num_map >= len(g.lanes)
    pose, _, valid = g.map_tokens(CFG.num_map, CFG.map_feat_dim)
    tok = pose[valid]
    for li, lane in enumerate(g.lanes):
        d = np.linalg.norm(tok[:, :2] - lane.points[0], axis=-1)
        assert d.min() < 1e-4, f"lane {li} has no token at its entry"


def test_offroad_query_ignores_crosswalks():
    """A vehicle standing on the crosswalk, away from the driving lanes,
    is off-road: the metric measures distance to kind='lane' only."""
    s = registry.generate_scene("pedestrian_crossing", 0, 0, cfg=CFG)
    g = s.lane_graph
    on_crosswalk = np.array([0.0, 6.0])       # mid-crosswalk, off both lanes
    assert g.distance(on_crosswalk) < 1.0
    assert g.distance(on_crosswalk, kinds=("lane",)) > 3.5
    assert not g.on_road(on_crosswalk, kinds=("lane",))


def test_spaced_starts_honors_min_gap():
    from repro.scenarios.policies import spaced_starts

    rng = np.random.default_rng(0)
    for n, lo, hi, gap in [(8, 10.0, 108.0, 18.0), (3, 0.0, 200.0, 10.0),
                           (5, 0.0, 12.0, 10.0)]:
        starts = spaced_starts(rng, n, lo, hi, min_gap=gap)
        assert 1 <= len(starts) <= n
        if len(starts) > 1:
            assert np.diff(starts).min() >= gap - 1e-4, (n, lo, hi, gap)


def test_map_tokens_masked_and_capped():
    s = registry.generate_scene("onramp_merge", seed=0, index=0, cfg=CFG)
    pose, feats, valid = s.lane_graph.map_tokens(CFG.num_map,
                                                 CFG.map_feat_dim)
    assert pose.shape == (CFG.num_map, 3)
    n = int(valid.sum())
    assert 0 < n <= CFG.num_map
    assert valid[:n].all() and not valid[n:].any()
    assert (pose[~valid] == 0).all()


# ---------------------------------------------------------------------------
# freeform back-compat shims
# ---------------------------------------------------------------------------

def test_freeform_shim_matches_registry():
    from repro.data import scenarios as data_scen

    legacy = data_scen.generate_scene(5, 9, CFG)
    fam = registry.generate_scene("freeform", 5, 9, CFG)
    for k in legacy:
        np.testing.assert_array_equal(legacy[k], fam.tensors[k], err_msg=k)
    batch = data_scen.generate_batch(5, 0, 3, CFG)
    assert batch["agent_pose"].shape == (3, CFG.num_steps, CFG.num_agents, 3)


def test_shared_kinematics_is_single_implementation():
    from repro.core import kinematics
    from repro.data import scenarios as data_scen
    from repro.runtime import rollout

    rng = np.random.default_rng(0)
    pose = rng.normal(size=(5, 3)).astype(np.float32)
    speed = np.abs(rng.normal(size=5)).astype(np.float32)
    p_np, s_np = data_scen.step_kinematics(pose, speed, 1.0, 0.1)
    p_j, s_j = rollout.step_kinematics(jnp.asarray(pose), jnp.asarray(speed),
                                       1.0, 0.1)
    p_c, s_c = kinematics.step_kinematics(pose, speed, 1.0, 0.1)
    np.testing.assert_allclose(np.asarray(p_j), p_np, atol=1e-6)
    np.testing.assert_array_equal(p_c, p_np)
    np.testing.assert_array_equal(s_c, s_np)


# ---------------------------------------------------------------------------
# variable agent counts through the model + engine
# ---------------------------------------------------------------------------

def _tiny_model(encoding="se2_fourier"):
    cfg = AgentSimConfig(d_model=32, num_layers=2, num_heads=2, head_dim=12,
                         d_ff=64, num_actions=CFG.num_actions,
                         encoding=encoding, fourier_terms=8, attn_impl="ref")
    model = AgentSimModel(cfg)
    params = nnm.init_params(model.specs(), jax.random.key(0))
    return model, params


def _scene_with_padding():
    """A scene whose valid agent count is strictly below the cap."""
    for idx in range(20):
        s = registry.generate_scene("onramp_merge", 2, idx, CFG)
        if 0 < s.num_valid_agents < CFG.num_agents:
            return s
    raise AssertionError("no padded scene found")


def test_padded_agents_do_not_change_valid_logits():
    """Physically removing the padding slots must not change any valid
    agent's logits — masking, not magic values, carries the variable
    agent count through attention."""
    model, params = _tiny_model()
    s = _scene_with_padding()
    n = s.num_valid_agents
    full = {k: jnp.asarray(v)[None] for k, v in s.tensors.items()}
    trimmed = dict(full)
    for k in ("agent_feats", "agent_pose", "agent_valid", "actions"):
        trimmed[k] = full[k][:, :, :n]
    lf, _ = model(params, full)
    lt, _ = model(params, trimmed)
    np.testing.assert_allclose(np.asarray(lf[:, :, :n], np.float32),
                               np.asarray(lt, np.float32),
                               atol=2e-4, rtol=2e-3)


def test_padded_agents_masked_through_prefill_step():
    """The cached decode path (prefill + per-step decode) agrees with the
    full forward on valid agents when padding slots ride along."""
    model, params = _tiny_model()
    s = _scene_with_padding()
    n = s.num_valid_agents
    batch = {k: jnp.asarray(v)[None] for k, v in s.tensors.items()}
    full, _ = model(params, batch)
    t_hist = 4
    cache = model.init_cache(1, CFG.num_map + CFG.num_steps * CFG.num_agents)
    hist = dict(batch)
    for k in ("agent_feats", "agent_pose", "agent_valid"):
        hist[k] = batch[k][:, :t_hist]
    got, cache = model.prefill(params, cache, hist)
    np.testing.assert_allclose(
        np.asarray(got[:, :, :n], np.float32),
        np.asarray(full[:, :t_hist, :n], np.float32), atol=2e-4, rtol=2e-3)
    for t in range(t_hist, CFG.num_steps):
        lt, cache = model.step(params, cache, batch["agent_feats"][:, t],
                               batch["agent_pose"][:, t],
                               batch["agent_valid"][:, t],
                               jnp.full((1,), t, jnp.int32))
        np.testing.assert_allclose(
            np.asarray(lt[:, :n], np.float32),
            np.asarray(full[:, t, :n], np.float32), atol=2e-4, rtol=2e-3)


def test_engine_freezes_invalid_agents():
    """RolloutEngine must not integrate padding slots: their 'poses' stay
    at the last history value for the whole rollout."""
    model, params = _tiny_model()
    s = _scene_with_padding()
    n = s.num_valid_agents
    t_hist = CFG.num_steps // 2
    engine = RolloutEngine(model, params, CFG, num_slots=2)
    fut = engine.run([s], t_hist=t_hist, n_samples=2, seed=0)
    last_hist = s.tensors["agent_pose"][t_hist - 1]
    for pad in range(n, CFG.num_agents):
        np.testing.assert_array_equal(
            fut[0, :, :, pad], np.broadcast_to(
                last_hist[pad], fut[0, :, :, pad].shape))
    # valid agents do move
    assert np.abs(fut[0, :, -1, :n, :2]
                  - last_hist[:n, :2]).max() > 1e-3


# ---------------------------------------------------------------------------
# mask-aware metrics
# ---------------------------------------------------------------------------

def test_rollout_metrics_exclude_invalid_agents():
    t, a, k = 6, 4, 3
    rng = np.random.default_rng(0)
    gt = rng.normal(size=(t, a, 3)).astype(np.float32)
    fut = np.repeat(gt[None], k, axis=0) + 0.1
    behavior = np.array([1, 1, 1, 1], np.int32)
    valid = np.ones((t, a), bool)
    valid[:, -1] = False
    fut_bad = fut.copy()
    fut_bad[:, :, -1, :2] += 1e6          # poison the padding slot
    clean = scenarios.rollout_metrics(CFG, gt, fut, behavior,
                                      agent_valid=valid)
    masked = scenarios.rollout_metrics(CFG, gt, fut_bad, behavior,
                                       agent_valid=valid)
    legacy = scenarios.rollout_metrics(CFG, gt, fut_bad, behavior)
    assert masked["straight"] == pytest.approx(clean["straight"])
    assert legacy["straight"] > 1e4       # the bug the mask fixes


def test_evaluation_metrics_shape():
    model, params = _tiny_model()
    scenes = [registry.generate_scene(f, 0, i, CFG)
              for f in ("highway", "pedestrian_crossing") for i in range(2)]
    engine = RolloutEngine(model, params, CFG, num_slots=4)
    res = evaluate_scenes(engine, scenes,
                          EvalConfig(t_hist=5, n_samples=2, seed=1))
    assert set(res) == {"highway", "pedestrian_crossing", "overall"}
    for fam, m in res.items():
        assert np.isfinite(m["min_ade"])
        assert 0.0 <= m["collision_rate"] <= 1.0
        assert m["kinematic_infeasibility_rate"] <= 1e-9
    assert res["overall"]["n_scenes"] == 4


# ---------------------------------------------------------------------------
# SE(2) property: re-posing a scene leaves closed-loop eval metrics alone
# ---------------------------------------------------------------------------

_ENGINE_CACHE = {}


def _eval_engine():
    if "e" not in _ENGINE_CACHE:
        model, params = _tiny_model("se2_repr")   # exact invariance
        _ENGINE_CACHE["e"] = RolloutEngine(model, params, CFG,
                                           num_slots=len(FAMILIES) * 2)
    return _ENGINE_CACHE["e"]


def _check_metrics_invariant(zx, zy, zth):
    """Re-posing every pose in a scene (map, agents, lane graph) by one
    rigid transform must leave every closed-loop eval metric of an
    SE(2)-relative model unchanged: the sampled action streams coincide
    (same per-(scene, sample) keys, invariant logits) and all metrics are
    functions of relative geometry only."""
    z = np.array([zx, zy, zth], np.float32)
    engine = _eval_engine()
    eval_cfg = EvalConfig(t_hist=CFG.num_steps // 2, n_samples=2, seed=5)
    scenes = [registry.generate_scene(f, 11, 0, CFG) for f in FAMILIES]
    moved = [scenarios.transform_scene(s, z) for s in scenes]
    base_m = evaluate_scenes(engine, scenes, eval_cfg)
    moved_m = evaluate_scenes(engine, moved, eval_cfg)
    for fam in base_m:
        for metric in ("min_ade", "miss_rate", "collision_rate",
                       "offroad_rate", "kinematic_infeasibility_rate"):
            b, m = base_m[fam][metric], moved_m[fam][metric]
            if np.isnan(b) and np.isnan(m):
                continue
            np.testing.assert_allclose(
                m, b, atol=0.1 if metric == "min_ade" else 0.15,
                err_msg=f"{fam}/{metric} moved under z={z}")


try:
    from hypothesis import given, settings, strategies as st

    transl = st.floats(min_value=-4.0, max_value=4.0, allow_nan=False,
                       width=32)
    angle = st.floats(min_value=-float(np.float32(np.pi)),
                      max_value=float(np.float32(np.pi)), allow_nan=False,
                      width=32)

    @settings(max_examples=3, deadline=None, derandomize=True)
    @given(zx=transl, zy=transl, zth=angle)
    def test_eval_metrics_se2_invariant_all_families(zx, zy, zth):
        _check_metrics_invariant(zx, zy, zth)

except ImportError:            # hypothesis is an optional dev dep:
    @pytest.mark.parametrize(  # fall back to fixed transforms
        "zx,zy,zth",
        [(0.0, 0.0, np.pi / 2), (3.0, -2.0, 0.7), (-4.0, 3.5, -2.9)])
    def test_eval_metrics_se2_invariant_all_families(zx, zy, zth):
        _check_metrics_invariant(zx, zy, zth)
