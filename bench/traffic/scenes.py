"""Scene generator: traffic scenes in the model's tensor layout, from a seed.

A scene is a lane map (polylines of short segments) and agents driving
near it. Each agent follows a persistent random walk over the action grid,
integrated with the benchmark's own unicycle step (``reference.kinematics``),
so every agent has a plausible history to stream. The work a scene
costs the model depends only on its caps (map tokens, agents, steps), never
on the drawn contents, so every seed asks for the same work.

Tensors (float32 / bool / int32): map_feats (M, 8), map_pose (M, 3),
map_valid (M,), agent_feats (T, A, 8), agent_pose (T, A, 3),
agent_valid (T, A), actions (T, A).
"""
from __future__ import annotations

import numpy as np

from bench.reference import kinematics

FEAT_DIM = 8
SEGMENTS_PER_LANE = 16


def _lane_map(rng, m, n_valid, radius):
    n_lanes = -(-n_valid // SEGMENTS_PER_LANE)
    start = rng.uniform(-radius, radius, (n_lanes, 2))
    heading = rng.uniform(-np.pi, np.pi, n_lanes)
    seg_len = rng.uniform(2.0, 6.0, (n_lanes, SEGMENTS_PER_LANE))
    curv = rng.normal(0.0, 0.02, (n_lanes, SEGMENTS_PER_LANE))
    theta = heading[:, None] + np.cumsum(curv * seg_len, axis=1)
    step = np.stack([np.cos(theta), np.sin(theta)], -1) * seg_len[..., None]
    mid = start[:, None] + np.cumsum(step, axis=1) - 0.5 * step
    feats = np.zeros((n_lanes, SEGMENTS_PER_LANE, FEAT_DIM), np.float32)
    feats[..., 0] = seg_len / 10.0
    feats[..., 1] = curv * 50.0
    kind = rng.random(n_lanes)
    feats[..., 2] = (kind < 0.9)[:, None]
    feats[..., 3] = np.arange(SEGMENTS_PER_LANE) / SEGMENTS_PER_LANE
    feats[..., 4] = (kind >= 0.9)[:, None]
    feats[..., 5] = rng.choice([1.4, 2.5, 3.5], n_lanes)[:, None]
    pose = np.concatenate([mid, theta[..., None]], -1).reshape(-1, 3)
    map_pose = np.zeros((m, 3), np.float32)
    map_feats = np.zeros((m, FEAT_DIM), np.float32)
    map_pose[:n_valid] = pose[:n_valid]
    map_feats[:n_valid] = feats.reshape(-1, FEAT_DIM)[:n_valid]
    valid = np.arange(m) < n_valid
    return map_pose, map_feats, valid, pose[:n_valid]


def make_scene(rng, shape, grid):
    """One scene. ``shape``: num_map, num_agents, num_steps, and the
    inclusive ranges ``map_valid`` and ``agents_valid`` the valid counts
    are drawn from; ``radius`` in metres."""
    m, a, t = shape["num_map"], shape["num_agents"], shape["num_steps"]
    n_map = int(rng.integers(shape["map_valid"][0], shape["map_valid"][1] + 1))
    n = int(rng.integers(shape["agents_valid"][0],
                         shape["agents_valid"][1] + 1))
    map_pose, map_feats, map_valid, lanes = _lane_map(rng, m, n_map,
                                                      shape["radius"])
    at = lanes[rng.integers(0, len(lanes), n)]
    pose = np.zeros((a, 3), np.float32)
    pose[:n, :2] = at[:, :2] + rng.normal(0.0, 1.5, (n, 2))
    pose[:n, 2] = at[:, 2] + rng.normal(0.0, 0.1, n)
    speed = np.zeros(a, np.float32)
    speed[:n] = rng.uniform(0.0, 15.0, n)
    valid = np.arange(a) < n
    vehicle = rng.random(a) < 0.8
    static = np.zeros((a, FEAT_DIM), np.float32)
    static[:, 1] = vehicle
    static[:, 2] = ~vehicle
    static[:, 3] = speed / 10.0
    static[:, 4] = rng.integers(0, 3, a) / 2.0
    static[~valid] = 0.0

    ai = rng.integers(0, grid["accel_bins"], a)
    yi = rng.integers(0, grid["yaw_bins"], a)
    poses = np.zeros((t, a, 3), np.float32)
    feats = np.zeros((t, a, FEAT_DIM), np.float32)
    actions = np.zeros((t, a), np.int32)
    for step in range(t):
        keep = rng.random((2, a)) < 0.8
        ai = np.where(keep[0], ai, np.clip(ai + rng.integers(-1, 2, a), 0,
                                           grid["accel_bins"] - 1))
        yi = np.where(keep[1], yi, np.clip(yi + rng.integers(-1, 2, a), 0,
                                           grid["yaw_bins"] - 1))
        act = (ai * grid["yaw_bins"] + yi).astype(np.int32)
        if step:
            pose, speed = kinematics(grid, pose, speed, act, valid)
        poses[step] = pose
        feats[step] = static
        feats[step, :, 0] = speed / 10.0
        actions[step] = np.where(valid, act, 0)
    feats[:, ~valid] = 0.0
    poses[:, ~valid] = 0.0
    return {
        "map_feats": map_feats, "map_pose": map_pose, "map_valid": map_valid,
        "agent_feats": feats, "agent_pose": poses,
        "agent_valid": np.broadcast_to(valid, (t, a)).copy(),
        "actions": actions,
    }


def make_scenes(seed: int, shape, grid, n: int):
    rng = np.random.default_rng(seed)
    return [make_scene(rng, shape, grid) for _ in range(n)]

