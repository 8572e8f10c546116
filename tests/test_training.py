"""Training-subsystem tests: expert-demonstration data contract, BC train
step, sim-arch registry, and the SE(2) *training* invariance property —
globally re-posing a scene leaves the behavior-cloning loss unchanged for
relative encodings and measurably changed for the ``absolute`` baseline
(the trained comparison's premise, property-tested before any training).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import scenarios
from repro.configs import SIM_ARCH_NAMES, get_sim_arch
from repro.data.pipeline import ShardedIterator
from repro.nn import module as nnm
from repro.nn.agent_sim import AgentSimConfig, AgentSimModel, action_nll
from repro.optim import adamw, chain, clip_by_global_norm
from repro.training.data import (TRAIN_KEYS, holdout_batches, make_batch_fn,
                                 make_sim_batch)
from repro.training.steps import (make_sim_eval_step, make_sim_train_step,
                                  open_loop_metrics, sim_input_specs)

SCEN = scenarios.ScenarioConfig(num_map=12, num_agents=4, num_steps=8)


def _tiny_model(encoding="se2_fourier", seed=0):
    cfg = AgentSimConfig(d_model=32, num_layers=2, num_heads=2, head_dim=12,
                         d_ff=64, num_actions=SCEN.num_actions,
                         encoding=encoding, fourier_terms=12,
                         attn_impl="ref")
    model = AgentSimModel(cfg)
    params = nnm.init_params(model.specs(), jax.random.key(seed))
    return model, params


def _device_batch(b):
    return {k: jnp.asarray(v) for k, v in b.items()}


# ---------------------------------------------------------------------------
# data contract
# ---------------------------------------------------------------------------

def test_sim_batch_shapes_keys_and_determinism():
    a = make_sim_batch(3, 16, 4, SCEN)
    b = make_sim_batch(3, 16, 4, SCEN)
    assert set(a) == set(TRAIN_KEYS)
    for k in a:
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    c = make_sim_batch(3, 20, 4, SCEN)
    assert any(not np.array_equal(a[k], c[k])
               for k in ("agent_pose", "map_pose"))
    # shapes match the abstract specs the dry-run lowers
    specs = sim_input_specs(SCEN, 4)
    for k, v in a.items():
        assert specs[k].shape == v.shape, k
    # action labels live in the model vocabulary
    assert a["actions"].dtype == np.int32
    assert a["actions"].min() >= 0
    assert a["actions"].max() < SCEN.num_actions


def test_sim_batch_mixes_families():
    """Consecutive indices cycle the registered families: within one batch
    spanning len(families) indices, at least two map layouts differ in
    their valid-token counts or geometry."""
    n_fam = len(scenarios.registry.names())
    b = make_sim_batch(0, 0, n_fam, SCEN)
    pose = b["map_pose"].reshape(n_fam, -1)
    assert len({arr.tobytes() for arr in pose}) > 1


def test_sharded_iterator_resume_preserves_data_order():
    it = ShardedIterator(make_batch_fn(SCEN), batch_size=2, seed=5)
    for _ in range(3):
        next(it)
    state = it.state_dict()
    expect = [next(it) for _ in range(2)]
    it.close()
    it2 = ShardedIterator(make_batch_fn(SCEN), batch_size=2, seed=5)
    it2.load_state_dict(state)
    got = [next(it2) for _ in range(2)]
    it2.close()
    for e, g in zip(expect, got):
        for k in e:
            np.testing.assert_array_equal(e[k], g[k], err_msg=k)
    assert state["batch_size"] == 2 and state["world"] == 1


def test_holdout_disjoint_from_training_stream():
    train = make_sim_batch(0, 0, 2, SCEN)
    held = holdout_batches(SCEN, 2, 1, seed=0)[0]
    assert not np.array_equal(train["agent_pose"], held["agent_pose"])


# ---------------------------------------------------------------------------
# train / eval steps
# ---------------------------------------------------------------------------

def test_train_step_reduces_loss_and_reports_metrics():
    model, params = _tiny_model()
    opt = chain(clip_by_global_norm(1.0), adamw(3e-3))
    step = jax.jit(make_sim_train_step(model, opt))
    opt_state = opt.init(params)
    mk = make_batch_fn(SCEN)
    losses = []
    for i in range(12):
        batch = _device_batch(mk(0, i * 2, 2))
        params, opt_state, m = step(params, opt_state, batch)
        losses.append(float(m["loss"]))
        assert np.isfinite(losses[-1])
        assert np.isfinite(float(m["grad_norm"]))
        assert 0.0 <= float(m["accuracy"]) <= 1.0
    assert np.mean(losses[-3:]) < np.mean(losses[:3]) - 0.1, losses


def test_eval_step_matches_action_nll():
    model, params = _tiny_model()
    batch = _device_batch(make_sim_batch(1, 0, 2, SCEN))
    out = jax.jit(make_sim_eval_step(model))(params, batch)
    logits, _ = model(params, batch)
    direct = action_nll(logits, batch["actions"], batch["agent_valid"])
    np.testing.assert_allclose(float(out["nll"]), float(direct), rtol=1e-6)
    m = open_loop_metrics(model, params, [make_sim_batch(1, 0, 2, SCEN)])
    np.testing.assert_allclose(m["nll"], float(direct), rtol=1e-6)


def test_loss_masks_padding_agents():
    """Poisoning an invalid agent's action labels must not move the loss
    (the mask is what makes variable-agent-count batches trainable)."""
    model, params = _tiny_model()
    batch = make_sim_batch(2, 0, 2, SCEN)
    # ensure there is at least one padding slot to poison
    batch["agent_valid"] = batch["agent_valid"].copy()
    batch["agent_valid"][:, :, -1] = False
    bad = {k: v.copy() for k, v in batch.items()}
    bad["actions"][:, :, -1] = SCEN.num_actions - 1
    eval_fn = jax.jit(make_sim_eval_step(model))
    a = float(eval_fn(params, _device_batch(batch))["nll"])
    b = float(eval_fn(params, _device_batch(bad))["nll"])
    assert a == pytest.approx(b, abs=1e-6)


# ---------------------------------------------------------------------------
# sim-arch registry
# ---------------------------------------------------------------------------

def test_sim_arch_registry():
    assert set(SIM_ARCH_NAMES) == {"sim-absolute", "sim-rope2d",
                                   "sim-se2-repr", "sim-se2-fourier"}
    with pytest.raises(KeyError):
        get_sim_arch("sim-nope")
    for name in SIM_ARCH_NAMES:
        arch = get_sim_arch(name)
        cfg = arch.agent_sim_config()
        scen = arch.scenario_config()
        assert cfg.num_actions == scen.num_actions
        small = arch.reduced()
        n = nnm.count_params(AgentSimModel(small.agent_sim_config()).specs())
        assert n < 1e6, (name, n)


def test_sim_arch_reduced_trains_one_step():
    arch = get_sim_arch("sim-se2-repr").reduced(num_map=8, num_agents=3,
                                                num_steps=6)
    model = AgentSimModel(arch.agent_sim_config())
    params = nnm.init_params(model.specs(), jax.random.key(0))
    opt = chain(clip_by_global_norm(1.0), adamw(1e-3))
    step = jax.jit(make_sim_train_step(model, opt))
    batch = _device_batch(make_sim_batch(0, 0, 2, arch.scenario_config()))
    p1, _, m = step(params, opt.init(params), batch)
    assert np.isfinite(float(m["loss"]))
    delta = max(float(jnp.max(jnp.abs(a - b)))
                for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(p1)))
    assert delta > 0


# ---------------------------------------------------------------------------
# SE(2) property: re-posing a scene leaves the TRAINING loss unchanged for
# relative encodings (and changed for the absolute baseline)
# ---------------------------------------------------------------------------

_LOSS_CACHE = {}
#: families whose scenes stay within the basis budget ``pos_scale`` targets
#: (encoder-scaled |(x, y)| <= 3.54 here, plus <= 0.28 from the re-posing
#: below); highway and on-ramp scenes reach 5.4 and are not in the domain
#: the Fourier truncation is documented for
_IN_BUDGET_FAMILIES = ("freeform", "pedestrian_crossing")


def _training_loss(encoding, z):
    """BC loss of a fixed random model on one batch re-posed by z."""
    if encoding not in _LOSS_CACHE:
        model, params = _tiny_model(encoding, seed=7)
        batch = _device_batch(make_sim_batch(11, 0, 2, SCEN,
                                             _IN_BUDGET_FAMILIES))
        eval_fn = jax.jit(make_sim_eval_step(model))
        _LOSS_CACHE[encoding] = (batch, eval_fn, params)
    batch, eval_fn, params = _LOSS_CACHE[encoding]
    moved = dict(batch)
    moved["map_pose"] = jnp.asarray(
        scenarios.transform_poses(z, np.asarray(batch["map_pose"])))
    moved["agent_pose"] = jnp.asarray(
        scenarios.transform_poses(z, np.asarray(batch["agent_pose"])))
    return float(eval_fn(params, moved)["nll"])


def _check_training_invariance(zx, zy, zth):
    z = np.array([zx, zy, zth], np.float32)
    e = np.zeros(3, np.float32)
    # se2_repr is exact (f32 roundoff); se2_fourier adds truncation error
    for encoding, tol in (("se2_repr", 1e-3), ("se2_fourier", 5e-3)):
        base = _training_loss(encoding, e)
        moved = _training_loss(encoding, z)
        assert abs(moved - base) < tol, (encoding, base, moved, z)
    if abs(zx) + abs(zy) > 1.0 or abs(zth) > 0.5:
        base = _training_loss("absolute", e)
        moved = _training_loss("absolute", z)
        assert abs(moved - base) > 1e-4, \
            f"absolute loss suspiciously invariant under z={z}"


try:
    from hypothesis import given, settings, strategies as st

    transl = st.floats(min_value=-4.0, max_value=4.0, allow_nan=False,
                       width=32)
    angle = st.floats(min_value=-float(np.float32(np.pi)),
                      max_value=float(np.float32(np.pi)), allow_nan=False,
                      width=32)

    @settings(max_examples=5, deadline=None, derandomize=True)
    @given(zx=transl, zy=transl, zth=angle)
    def test_training_loss_se2_invariant(zx, zy, zth):
        _check_training_invariance(zx, zy, zth)

except ImportError:            # hypothesis is an optional dev dep:
    @pytest.mark.parametrize(  # fall back to fixed transforms
        "zx,zy,zth",
        [(0.0, 0.0, np.pi / 2), (3.0, -2.0, 0.7), (-4.0, 3.5, -2.9)])
    def test_training_loss_se2_invariant(zx, zy, zth):
        _check_training_invariance(zx, zy, zth)


def test_rope2d_training_loss_translation_invariant():
    """rope2d is the translation-only row of Table I: invariant to shifts,
    NOT to rotations — both directions checked so the registry's claims
    stay honest."""
    shift = _training_loss("rope2d", np.array([5.0, -3.0, 0.0], np.float32))
    base = _training_loss("rope2d", np.zeros(3, np.float32))
    assert abs(shift - base) < 1e-3, (base, shift)
    rot = _training_loss("rope2d", np.array([0.0, 0.0, 1.2], np.float32))
    assert abs(rot - base) > 1e-4, (base, rot)
