"""The comparison that decides ``correct`` in the serving cells.

Each sampled lane's served trajectory is fed back to the reference as
tokens (the scene's history, then the served poses with the speeds the
served actions imply), which gives the reference's logits at every step the
server sampled. Sampling is Gumbel-max with the lane's documented key, so
the reference adds the same Gumbel noise and reads, for every valid agent
and step, how far the served action's perturbed logit lies below the best
one (``action_gap``; 0 where they agree). Separately, each served pose is
checked against one unicycle step from the previous pose with the served
action (``kinematics_residual``, the largest difference of x, y in metres
or theta in radians).

The control (``control=True``) is the reference one step below the
precision the configurations state, put in the program's place. The model
with its matmul operands rounded to float8_e4m3's 3 mantissa bits (the
configurations state bfloat16 operands): at each step of the same lanes it
picks the action its logits put first under the same noise, and that
action's gap under the float32 reference is its ``action_gap``. The
unicycle step in bfloat16 arithmetic (the configurations state float32):
each step's increments are computed in bfloat16 and added to the float32
world pose, the program's pose representation, and its
``kinematics_residual`` is the served poses' distance from those steps.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from bench import reference
from bench.weights import make_params

#: stored mantissa bits of the control's matmul operands (float8_e4m3)
CONTROL_BITS = 3


def _bf16(x):
    return np.asarray(np.asarray(x, np.float32).astype(ml_dtypes.bfloat16),
                      np.float32)


def kinematics_bf16(grid, pose, speed, action, valid):
    """``reference.kinematics`` with every operation of the step rounded to
    bfloat16, its increments added to the float32 pose."""
    accel = np.linspace(-grid["max_accel"], grid["max_accel"],
                        grid["accel_bins"])
    yaw = np.linspace(-grid["max_yaw_rate"], grid["max_yaw_rate"],
                      grid["yaw_bins"])
    ai, yi = np.divmod(action, grid["yaw_bins"])
    dt = grid["dt"]
    speed = _bf16(speed)
    new_speed = _bf16(np.clip(_bf16(speed + _bf16(accel[ai] * dt)), 0.0,
                              grid["max_speed"]))
    th = (pose[:, 2] + _bf16(yaw[yi] * dt)).astype(np.float32)
    step = _bf16(_bf16(0.5 * _bf16(speed + new_speed)) * dt)
    new_pose = np.stack([pose[:, 0] + _bf16(step * _bf16(np.cos(_bf16(th)))),
                         pose[:, 1] + _bf16(step * _bf16(np.sin(_bf16(th)))),
                         th], -1).astype(np.float32)
    return (np.where(valid[:, None], new_pose, pose),
            np.where(valid, new_speed, speed))


def served_scene(grid, lane):
    """(reference scene tensors, kinematics residual, the control's) of one
    served lane."""
    sc, th, tt = lane["scene"], lane["t_hist"], lane["t_total"]
    feats = sc["agent_feats"][:tt].copy()
    pose = sc["agent_pose"][:tt].copy()
    valid = sc["agent_valid"][:tt].copy()
    v = sc["agent_valid"][th - 1]
    proto = sc["agent_feats"][th - 1]
    p = sc["agent_pose"][th - 1]
    speed = sc["agent_feats"][th - 1, :, 0] * np.float32(10.0)
    resid = ctl = 0.0
    for i, t in enumerate(range(th, tt)):
        act = lane["actions"][i]
        low, _ = kinematics_bf16(grid, p, speed, act, v)
        pred, speed = reference.kinematics(grid, p, speed, act, v)
        served = lane["future"][i]
        if v.any():
            resid = max(resid, float(np.max(np.abs(served[v] - pred[v]))))
            ctl = max(ctl, float(np.max(np.abs(served[v] - low[v]))))
        p = served
        pose[t], valid[t] = served, v
        feats[t] = proto
        feats[t, :, 0] = speed / np.float32(10.0)
    scene = {"map_feats": sc["map_feats"], "map_pose": sc["map_pose"],
             "map_valid": sc["map_valid"], "agent_feats": feats,
             "agent_pose": pose, "agent_valid": valid}
    return {k: jnp.asarray(x) for k, x in scene.items()}, resid, ctl


def _gaps(pert_ref, picked, valid):
    """How far each picked action's perturbed reference logit lies below
    the best, over valid agents: (steps, A) -> flat array."""
    best = pert_ref.max(-1)
    got = np.take_along_axis(pert_ref, picked[..., None], -1)[..., 0]
    return (best - got)[:, valid].ravel()


def _widest(gaps):
    return float(max((g.max(initial=0.0) for g in gaps),
                     default=float("inf")))


def serve_lanes(cell, sample, *, control=False):
    """Readings of the served lanes, {name: value}, and the control's
    readings in the same form (None without ``control``)."""
    model, grid = cell.model, cell.grid
    params = make_params(model, cell.seed)
    exact = jax.jit(lambda p, s: reference.logits(model, p, s))
    low = jax.jit(lambda p, s: reference.logits(model, p, s,
                                                operand_bits=CONTROL_BITS))
    gaps, ctl_gaps, resid, ctl_resid, tokens = [], [], 0.0, 0.0, 0
    for lane in sample:
        scene, r, rc = served_scene(grid, lane)
        resid, ctl_resid = max(resid, r), max(ctl_resid, rc)
        th, tt = lane["t_hist"], lane["t_total"]
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(exact(params, scene))
            lc = np.asarray(low(params, scene)) if control else None
        a, na = lg.shape[1], lg.shape[2]
        key = reference.lane_key(lane["req_seed"], lane["scene_id"],
                                 lane["sample_id"])
        noise = np.asarray(reference.gumbel_noise(
            key, jnp.arange(th, tt, dtype=jnp.int32), a, na))
        pert = lg[th - 1:tt - 1] + noise
        valid = np.asarray(lane["scene"]["agent_valid"][th - 1])
        g = _gaps(pert, lane["actions"].astype(np.int64), valid)
        gaps.append(g)
        tokens += g.size
        if control:
            pick = np.argmax(lc[th - 1:tt - 1] + noise, -1)
            ctl_gaps.append(_gaps(pert, pick, valid))
    out = {"action_gap": _widest(gaps), "kinematics_residual": resid,
           "tokens_compared": tokens}
    if not control:
        return out, None
    ctl = {"action_gap": _widest(ctl_gaps),
           "kinematics_residual": ctl_resid, "tokens_compared": tokens}
    out["mismatch_share"] = float(np.mean(np.concatenate(gaps) > 0))
    ctl["mismatch_share"] = float(np.mean(np.concatenate(ctl_gaps) > 0))
    return out, ctl
