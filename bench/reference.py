"""Plain reference of the agent-sim scene transformer, in ``jax.numpy``.

Written from the model's description (arXiv:2507.18597, Sec. III and IV-B)
and the configuration file, independently of the program under test: it
imports nothing from ``repro`` and takes nothing the program made. It
computes the same function as the program's model, token by token:

* tokens ``[map..., agents@t0, agents@t1, ...]``, each with a pose;
  attention is block-causal over times (map tokens at time 0, agents of
  step t at time t + 1) and masked by validity;
* pre-norm blocks: RMSNorm -> relative attention -> residual, RMSNorm ->
  SwiGLU -> residual, then a final RMSNorm and the action head;
* ``se2_fourier``: the paper's factorised SE(2) encoding (Alg. 2): each
  6-wide block of a head is acted on by rotations of its scaled x, y and
  theta, with the x and y parts expanded in F Fourier terms whose key-side
  coefficients come from a 2F-point quadrature;
* ``absolute``: Fourier features of (x, y, theta) through a learned
  projection, added to the token features.

The reference computes in float32, with every matmul at ``highest``
precision (the caller's ``jax.default_matmul_precision``).
``operand_bits`` rounds every matmul's operands to that many stored
mantissa bits first and keeps the rest in float32: the control of the
correctness check runs it at 3 bits, float8_e4m3's significand, one step
below the bfloat16 operands the configurations state (the exponent
range is not modelled, as if every operand were ideally scaled).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# -- the SE(2) Fourier encoding ------------------------------------------------

def _block_scales(model):
    nb = model["head_dim"] // 6
    lo, hi = model["min_scale"], model["max_scale"]
    if nb == 1:
        return np.array([hi])
    return np.exp(np.linspace(np.log(lo), np.log(hi), nb))


def _frequencies(f):
    i = np.arange(f)
    return np.where(i % 2 == 0, i // 2, (i + 1) // 2), i % 2 == 1


def _basis(theta, f):
    """[1, sin(z), cos(z), sin(2z), cos(2z), ...] of ``theta``: (..., F)."""
    freq, odd = _frequencies(f)
    z = theta[..., None] * jnp.asarray(freq, jnp.float32)
    return jnp.where(jnp.asarray(odd), jnp.sin(z), jnp.cos(z))


def _quadrature(f):
    """2F nodes on [-pi, pi) and the (2F, F) map from samples to
    coefficients of the basis above (the rectangle rule, exact for
    trigonometric polynomials of degree < F)."""
    nodes = -np.pi + 2.0 * np.pi * np.arange(2 * f) / (2 * f)
    freq, odd = _frequencies(f)
    g = np.where(odd[None], np.sin(nodes[:, None] * freq[None]),
                 np.cos(nodes[:, None] * freq[None]))
    weight = np.where(np.arange(f) == 0, 1.0, 2.0) / (2 * f)
    return nodes, g * weight[None]


def _scaled(model, pose):
    """pose (..., 3) in encoder units -> per-block x, y (..., nb), theta."""
    a = jnp.asarray(_block_scales(model), jnp.float32)
    return pose[..., 0:1] * a, pose[..., 1:2] * a, pose[..., 2]


def _rot(c, s, u0, u1):
    return u0 * c - u1 * s, u0 * s + u1 * c


def se2_query(model, x, pose):
    """x (..., hd) -> (..., nb * (4F + 2)) for queries."""
    f = model["fourier_terms"]
    nb = model["head_dim"] // 6
    xb = x.reshape(*x.shape[:-1], nb, 6)
    px, py, th = _scaled(model, pose)
    c, s = jnp.cos(th)[..., None], jnp.sin(th)[..., None]
    vx, vy = -px * c - py * s, px * s - py * c          # (..., nb)
    b = _basis(th, f)[..., None, :]                      # (..., 1, F)
    parts = []
    for v, i in ((vx, 0), (vy, 2)):
        r0, r1 = _rot(jnp.cos(v), -jnp.sin(v), xb[..., i], xb[..., i + 1])
        parts += [r0[..., None] * b, r1[..., None] * b]
    t0, t1 = _rot(c, s, xb[..., 4], xb[..., 5])
    parts += [t0[..., None], t1[..., None]]
    return jnp.concatenate(parts, -1).reshape(*x.shape[:-1], -1)


def se2_key(model, x, pose):
    """x (..., hd) -> (..., nb * (4F + 2)) for keys and values."""
    f = model["fourier_terms"]
    nb = model["head_dim"] // 6
    xb = x.reshape(*x.shape[:-1], nb, 6)
    px, py, th = _scaled(model, pose)
    nodes, proj = _quadrature(f)
    cz, sz = jnp.asarray(np.cos(nodes)), jnp.asarray(np.sin(nodes))
    proj = jnp.asarray(proj, jnp.float32)
    parts = []
    for u, i in ((px[..., None] * cz + py[..., None] * sz, 0),
                 (-px[..., None] * sz + py[..., None] * cz, 2)):
        gam, lam = jnp.cos(u) @ proj, jnp.sin(u) @ proj  # (..., nb, F)
        k0, k1 = xb[..., i:i + 1], xb[..., i + 1:i + 2]
        parts += [gam * k0 - lam * k1, lam * k0 + gam * k1]
    c, s = jnp.cos(th)[..., None], jnp.sin(th)[..., None]
    t0, t1 = _rot(c, s, xb[..., 4], xb[..., 5])
    parts += [t0[..., None], t1[..., None]]
    return jnp.concatenate(parts, -1).reshape(*x.shape[:-1], -1)


def se2_output(model, o, pose):
    """Attention output (..., nb * (4F + 2)) -> (..., hd) at the query."""
    f = model["fourier_terms"]
    nb = model["head_dim"] // 6
    ob = o.reshape(*o.shape[:-1], nb, 4 * f + 2)
    px, py, th = _scaled(model, pose)
    c, s = jnp.cos(th)[..., None], jnp.sin(th)[..., None]
    vx, vy = -px * c - py * s, px * s - py * c
    b = _basis(th, f)[..., None, :]
    outs = []
    for v, off in ((vx, 0), (vy, 2 * f)):
        top = jnp.sum(b * ob[..., off:off + f], -1)
        bot = jnp.sum(b * ob[..., off + f:off + 2 * f], -1)
        outs += list(_rot(jnp.cos(v), jnp.sin(v), top, bot))
    outs += list(_rot(c, -s, ob[..., 4 * f], ob[..., 4 * f + 1]))
    return jnp.stack(outs, -1).reshape(*o.shape[:-1], 6 * nb)


def pose_features(model, pose):
    """Absolute baseline: sin/cos of (x, y, theta) at 2**k, k < 8: (..., 48)."""
    ps = model["pos_scale"]
    scaled = jnp.stack([pose[..., 0] * ps, pose[..., 1] * ps, pose[..., 2]],
                       -1)
    ang = scaled[..., None] * jnp.asarray(2.0 ** np.arange(8), jnp.float32)
    feats = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
    return feats.reshape(*pose.shape[:-1], 48)


# -- the model -----------------------------------------------------------------

def _op(x, bits):
    """A matmul operand, its significand rounded to ``bits`` stored bits
    (None: as it is)."""
    x = x.astype(jnp.float32)
    if bits is None:
        return x
    m, e = jnp.frexp(x)                      # x = m * 2**e, |m| in [0.5, 1)
    step = float(2 ** (bits + 1))
    return jnp.ldexp(jnp.round(m * step) / step, e)


def _mm(a, b, bits):
    """Matmul contracting a's last axis with b's first, in float32."""
    return jnp.tensordot(_op(a, bits), _op(b, bits), axes=1)


def _rms(x, scale):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    return y * scale


def tokens(model, scene):
    """One scene's token features, poses, times and segment ids.

    scene: map_feats (M, Fm), map_pose (M, 3), map_valid (M,), agent_feats
    (T, A, Fa), agent_pose (T, A, 3), agent_valid (T, A)."""
    m = scene["map_feats"].shape[0]
    t, a = scene["agent_valid"].shape
    pose = jnp.concatenate([scene["map_pose"],
                            scene["agent_pose"].reshape(t * a, 3)])
    times = np.concatenate([np.zeros(m, np.int32),
                            np.repeat(np.arange(1, t + 1, dtype=np.int32), a)])
    valid = jnp.concatenate([scene["map_valid"],
                             scene["agent_valid"].reshape(t * a)])
    return pose.astype(jnp.float32), times, jnp.where(valid, 0, -1)


def _attention(model, q, k, v, times, seg, bits, q_block):
    """Block-causal masked attention of one scene, all heads.

    q (H, S, c), k (H, S, c), v (H, S, cv). Query rows go in blocks of
    ``q_block``; a block reads only keys up to the last token of its
    latest time (tokens are sorted by time), which is exact."""
    s = q.shape[1]
    scale = 1.0 / math.sqrt(model["head_dim"])
    seg = jnp.asarray(seg)

    def block(qb, kb, vb, qt, kt, qs, ks):
        mask = (kt[None, :] <= qt[:, None]) & (qs[:, None] == ks[None, :]) \
            & (ks[None, :] >= 0)
        sc = jnp.einsum("hnc,hmc->hnm", _op(qb, bits), _op(kb, bits)) * scale
        p = jax.nn.softmax(jnp.where(mask[None], sc, NEG_INF), -1)
        p = jnp.where(mask.any(-1)[None, :, None], p, 0.0)
        return jnp.einsum("hnm,hmc->hnc", _op(p, bits), _op(vb, bits))

    outs = []
    for r0 in range(0, s, q_block):
        r1 = min(s, r0 + q_block)
        kend = int(np.searchsorted(times, times[r1 - 1], side="right"))
        outs.append(block(q[:, r0:r1], k[:, :kend], v[:, :kend],
                          jnp.asarray(times[r0:r1]), jnp.asarray(times[:kend]),
                          seg[r0:r1], seg[:kend]))
    return jnp.concatenate(outs, 1)


def logits(model, params, scene, *, operand_bits=None, q_block=1024):
    """Action logits (T, A, num_actions) of every agent token of a scene;
    ``operand_bits``: the stored mantissa bits matmul operands are rounded
    to (None: float32's own)."""
    bits = operand_bits
    m = scene["map_feats"].shape[0]
    t, a = scene["agent_valid"].shape
    h, hd = model["num_heads"], model["head_dim"]
    pose, times, seg = tokens(model, scene)
    x = jnp.concatenate([
        _mm(scene["map_feats"], params["map_enc"]["kernel"], bits),
        _mm(scene["agent_feats"].reshape(t * a, -1),
            params["agent_enc"]["kernel"], bits)])
    se2 = model["encoding"] == "se2_fourier"
    if not se2:
        x = x + _mm(pose_features(model, pose),
                    params["pose_proj"]["kernel"], bits)
    ps = model["pos_scale"]
    pose_enc = pose * jnp.asarray([ps, ps, 1.0], jnp.float32)

    def block(x, lp):
        y = _rms(x, lp["norm1"]["scale"])
        q, k, v = (jnp.transpose(_mm(y, lp["attn"][n]["kernel"], bits),
                                 (1, 0, 2)) for n in "qkv")  # (H, S, hd)
        if se2:
            q = se2_query(model, q, pose_enc[None])
            k = se2_key(model, k, pose_enc[None])
            v = se2_key(model, v, pose_enc[None])
        o = _attention(model, q, k, v, times, seg, bits, q_block)
        if se2:
            o = se2_output(model, o, pose_enc[None])
        o = jnp.transpose(o, (1, 0, 2))                      # (S, H, hd)
        wo = lp["attn"]["o"]["kernel"].reshape(h * hd, -1)
        x = x + _mm(o.reshape(-1, h * hd), wo, bits)
        y = _rms(x, lp["norm2"]["scale"])
        g = _mm(y, lp["mlp"]["gate"]["kernel"], bits)
        u = _mm(y, lp["mlp"]["up"]["kernel"], bits)
        return x + _mm(jax.nn.silu(g) * u, lp["mlp"]["down"]["kernel"],
                       bits)

    x, _ = jax.lax.scan(lambda x, lp: (block(x, lp), None), x,
                        params["blocks"])
    x = _rms(x, params["final_norm"]["scale"])
    out = _mm(x[m:], params["head"]["kernel"], bits)
    return out.reshape(t, a, -1)


# -- kinematics and sampling -----------------------------------------------------

def kinematics(grid, pose, speed, action, valid):
    """One unicycle step on the action grid (midpoint speed), frozen where
    not valid. pose (A, 3), speed (A,), action (A,) ids."""
    accel = np.linspace(-grid["max_accel"], grid["max_accel"],
                        grid["accel_bins"])
    yaw = np.linspace(-grid["max_yaw_rate"], grid["max_yaw_rate"],
                      grid["yaw_bins"])
    ai, yi = np.divmod(action, grid["yaw_bins"])
    dt = grid["dt"]
    new_speed = np.clip(speed + accel[ai].astype(np.float32) * dt, 0.0,
                        grid["max_speed"]).astype(np.float32)
    th = pose[:, 2] + yaw[yi].astype(np.float32) * dt
    mid = 0.5 * (speed + new_speed)
    new_pose = np.stack([pose[:, 0] + mid * np.cos(th) * dt,
                         pose[:, 1] + mid * np.sin(th) * dt, th], -1)
    return (np.where(valid[:, None], new_pose, pose).astype(np.float32),
            np.where(valid, new_speed, speed).astype(np.float32))


def lane_key(req_seed, scene_id, sample_id):
    """The sampling stream of one lane: the serving API's documented key."""
    return jax.random.fold_in(
        jax.random.fold_in(jax.random.key(req_seed), scene_id), sample_id)


@functools.partial(jax.jit, static_argnums=(2, 3))
def gumbel_noise(key, steps, num_agents, num_actions):
    """Gumbel noise of each step t in ``steps``: what categorical sampling
    with ``fold_in(key, t)`` adds to the logits before its argmax."""
    return jax.vmap(lambda t: jax.random.gumbel(
        jax.random.fold_in(key, t), (num_agents, num_actions)))(steps)
