"""Batched closed-loop rollout engine over the cached SE(2) decode path.

The agent-simulation analogue of :class:`repro.runtime.server.Server`:
fixed scene slots, ONE jitted step advancing every slot in lockstep, and
per-slot cache cursors. Each engine tick appends one simulation step (A
agent tokens per scene) to every slot's K/V cache and runs the model's
incremental ``step`` — O(T) attention per tick instead of the O(T^2)
full-scene recompute the naive rollout pays (see ``docs/rollout.md`` and
``benchmarks/rollout_bench.py``).

Sampling is device-side and keyed per (scene, sample): slot ``(si, ki)``
draws from ``fold_in(fold_in(key(seed), si), ki)`` folded again with the
step index, so rollout metrics are bit-reproducible regardless of slot
assignment, chunking, or parallel execution order.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core import kinematics
from repro.distributed.sharding import without_mesh_rules
from repro.scenarios.core import ScenarioConfig

#: mesh axes a fleet engine partitions its scene slots over, in order
FLEET_AXES = ("pod", "data")


def step_kinematics(pose, speed, accel, yaw_rate,
                    dt: float = kinematics.DT):
    """jnp entry point of the shared unicycle integrator
    (:mod:`repro.core.kinematics`) so the whole engine tick (decode +
    sample + integrate) stays in one jitted device call. The host data
    pipeline calls the very same function on numpy arrays — one
    implementation, identical integration by construction."""
    return kinematics.step_kinematics(pose, speed, accel, yaw_rate, dt,
                                      xp=jnp)


def rollout_keys(seed: int, n_scenes: int, n_samples: int):
    """The per-(scene, sample) PRNG keys the engine samples with; exposed so
    baselines can consume the identical stream."""
    base = jax.random.key(seed)
    return jnp.stack([
        jax.random.fold_in(jax.random.fold_in(base, si), ki)
        for si in range(n_scenes) for ki in range(n_samples)])


class RolloutEngine:
    """Closed-loop simulation over fixed slots with cached incremental decode.

    One slot = one (scene, sample) rollout. ``run`` chunks an arbitrary
    workload over ``num_slots`` lanes; every chunk reuses the same jitted
    prefill/step (shapes are static), so there is exactly one compilation
    of each.
    """

    def __init__(self, model, params, scen_cfg: ScenarioConfig,
                 *, num_slots: int, max_len: Optional[int] = None,
                 cache_dtype=None, decode_impl: Optional[str] = None,
                 mesh=None, registry: Optional[obs.Registry] = None):
        """``cache_dtype``: storage dtype of the per-layer K/V cache — a
        jnp dtype or "float32" / "bfloat16" / "int8" (int8 caches carry
        per-row scales beside K/V and are dequantized inside the decode
        kernel; see ``AgentSimModel.init_cache``). ``decode_impl``
        overrides the model's decode attention backend for this engine
        ("auto" / "flash_decode" / "xla" / "ref" / "chunked" — see
        ``repro.kernels.ops.decode_attention``); None keeps the model
        config's choice.

        ``mesh``: optional scene-axis mesh (``launch.mesh.make_fleet_mesh``)
        carrying the DP axes in :data:`FLEET_AXES`. When set, the jitted
        prefill/tick are ``shard_map``-ed over the slot axis: ``num_slots``
        lanes partition over ``("pod", "data")`` (rounded UP to a multiple
        of the shard count — ``run`` already pads partial chunks), params
        replicate, and each device advances only its local lanes. Per-slot
        PRNG keys and validity masks are computed on the HOST exactly as in
        the single-device path, every lane's attention / sampling /
        integration is lane-local, and lanes never interact — so gathered
        per-scene outputs are bit-identical to the unsharded engine
        regardless of device count or slot placement
        (tests/test_distributed.py pins this on a forced CPU mesh).

        ``registry``: telemetry home (``repro.obs``) — ``None`` = the
        process default, ``obs.NULL`` = off. The engine records
        ``rollout.prefill`` / ``rollout.step`` / ``rollout.chunk`` spans
        (host wall-clock around the async dispatches — never a forced
        sync) and a ``rollout.cache_bytes`` gauge from shape metadata;
        obs-on vs obs-off runs are bit-identical (tests/test_obs.py).
        """
        self.obs = registry if registry is not None else obs.get_registry()
        self.model = model
        self.mesh = mesh
        self.params = params
        self.scen = scen_cfg
        self.num_slots = num_slots
        max_len = max_len or (scen_cfg.num_map
                              + scen_cfg.num_steps * scen_cfg.num_agents)
        # Round up to the decode kernel's key-block size: layer-stacked
        # caches are consumed in place (padding them per call would copy
        # the whole buffer every tick); unwritten rows stay cursor-masked.
        self.max_len = -(-max_len // 128) * 128 if max_len > 128 else max_len
        self.cache_dtype = cache_dtype
        self.decode_impl = decode_impl
        self._accel = jnp.asarray(scen_cfg.accel_values(), jnp.float32)
        self._yaw = jnp.asarray(scen_cfg.yaw_values(), jnp.float32)
        raw_prefill = functools.partial(model.prefill, impl=decode_impl)

        def prefill_fn(params, cache, batch):
            # named_scope is trace-time annotation only (shows up in XLA /
            # --profile-dir traces); it cannot change values or shapes
            with jax.named_scope("rollout.prefill"):
                return raw_prefill(params, cache, batch)

        step_fn = self._step_impl
        self._cache_shardings = None
        if mesh is not None:
            lane_axes = tuple(a for a in FLEET_AXES if a in mesh.shape)
            extra = [a for a in mesh.shape
                     if a not in lane_axes and mesh.shape[a] > 1]
            if not lane_axes or extra:
                raise ValueError(
                    f"fleet mesh must carry only the scene axes "
                    f"{FLEET_AXES}; got {dict(mesh.shape)}")
            shards = int(np.prod([mesh.shape[a] for a in lane_axes]))
            self.num_slots = -(-num_slots // shards) * shards
            lane = P(lane_axes if len(lane_axes) > 1 else lane_axes[0])
            # cache leaves: layer-stacked K/V rows carry the slot axis at
            # dim 1 (L, B, H, c, S); times/seg/cursor carry it at dim 0
            cache_struct = jax.eval_shape(self.init_cache)
            stacked = set(model._LAYER_CACHE_KEYS)
            cache_spec = {k: (P(None, *lane) if k in stacked else lane)
                          for k in cache_struct}
            self._cache_shardings = {
                k: NamedSharding(mesh, s) for k, s in cache_spec.items()}
            # each device advances only its own lanes: the logical rules of
            # an enclosing mesh (the training mesh, when the trainer's eval
            # hook runs the engine) do not describe that code
            lane_local = without_mesh_rules()
            prefill_fn = jax.shard_map(
                lane_local(prefill_fn), mesh=mesh,
                in_specs=(P(), cache_spec, lane),
                out_specs=(lane, cache_spec), check_vma=False)
            step_fn = jax.shard_map(
                lane_local(step_fn), mesh=mesh,
                in_specs=(P(), cache_spec) + (lane,) * 6 + (P(),),
                out_specs=(cache_spec,) + (lane,) * 4, check_vma=False)
        # Donate the cache so XLA updates it in place: without donation
        # every tick round-trips the full preallocated K/V cache through
        # a copy, which dwarfs the attention work the decode kernel
        # saves (the cache is tens of MiB per slot batch).
        # CostAccounted AOT-compiles on first call (still exactly one
        # trace/compilation — the zero-extra-compilation guards read its
        # _cache_size) and records compiled FLOPs/bytes as cost.* gauges.
        self._prefill = obs.CostAccounted(
            jax.jit(prefill_fn, donate_argnums=(1,)),
            "rollout.prefill", registry=self.obs)
        self._step = obs.CostAccounted(
            jax.jit(step_fn, donate_argnums=(1,)),
            "rollout.step", registry=self.obs)
        self.ticks = 0
        self.last_actions = None      # (S, K, T_fut, A) after each run()

    @property
    def params(self):
        return self._params

    @params.setter
    def params(self, params):
        # A fleet engine's shard_map takes the parameters replicated (P());
        # placing them here lets a caller hand in parameters laid out for
        # another mesh over the same devices (the training mesh, from the
        # trainer's eval hook) without the compiled step seeing a new
        # input sharding.
        if self.mesh is not None:
            params = jax.device_put(params, NamedSharding(self.mesh, P()))
        self._params = params

    def init_cache(self):
        cache = self.model.init_cache(self.num_slots, self.max_len,
                                      self.cache_dtype)
        if self._cache_shardings is not None:
            # place slot-sharded from the start, so the prefill donation
            # reuses the buffers instead of resharding a replicated copy
            cache = jax.device_put(cache, self._cache_shardings)
        # shape metadata only — no device read, no sync
        self.obs.gauge("rollout.cache_bytes").set(
            sum(int(np.prod(v.shape)) * v.dtype.itemsize
                for v in jax.tree.leaves(cache)))
        return cache

    def _step_impl(self, params, cache, logits, pose, speed, feats_proto,
                   valid, keys, t):
        """One engine tick, fully on device: sample an action per agent from
        the previous step's logits, integrate kinematics to produce sim-step
        ``t``'s poses, then decode the A new agent tokens against the cache
        to get the next sampling distribution.

        ``valid`` (B, A) marks each slot's real agents (families generate
        variable agent counts padded to A slots); invalid agents are frozen
        in place and their tokens enter the cache segment-masked, so they
        never influence attention or metrics.

        ``keys`` arrive as raw uint32 key DATA (B, 2), not typed key
        arrays: the fleet path shard_maps this function over the slot
        axis and plain arrays partition like any other per-lane input.
        ``wrap_key_data`` reconstructs the identical typed keys, so the
        sampled stream is unchanged."""
        with jax.named_scope("rollout.step"):
            return self._step_body(params, cache, logits, pose, speed,
                                   feats_proto, valid, keys, t)

    def _step_body(self, params, cache, logits, pose, speed, feats_proto,
                   valid, keys, t):
        b, a, _ = feats_proto.shape
        keys = jax.random.wrap_key_data(keys)
        keys_t = jax.vmap(jax.random.fold_in, in_axes=(0, None))(keys, t)
        acts = jax.vmap(jax.random.categorical)(
            keys_t, logits.astype(jnp.float32))           # (B, A)
        ai, yi = jnp.divmod(acts, self.scen.yaw_bins)
        new_pose, new_speed = step_kinematics(pose, speed, self._accel[ai],
                                              self._yaw[yi])
        pose = jnp.where(valid[..., None], new_pose, pose)
        speed = jnp.where(valid, new_speed, speed)
        feats = feats_proto.at[..., 0].set(speed / 10.0)
        t_vec = jnp.broadcast_to(t, (b,)).astype(jnp.int32)
        logits, cache = self.model.step(params, cache, feats, pose, valid,
                                        t_vec, impl=self.decode_impl)
        return cache, logits, pose, speed, acts

    def _run_chunk(self, hist_batch: Dict[str, jnp.ndarray], keys,
                   t_hist: int, t_total: int):
        """Roll ``num_slots`` independent (scene, sample) lanes forward from
        their history; returns sampled poses (B, t_total - t_hist, A, 3).

        Mirrors the full-recompute loop's structure exactly: the action for
        step t is sampled from the logits of the step t-1 agent tokens (the
        last history step's logits come from prefill), so the cached and
        recompute rollouts draw from the same distributions with the same
        per-(scene, sample) key stream.
        """
        cache = self.init_cache()
        with self.obs.span("rollout.prefill"):
            hist_logits, cache = self._prefill(self.params, cache,
                                               hist_batch)
        logits = hist_logits[:, -1]                        # (B, A, K)
        pose = hist_batch["agent_pose"][:, -1]
        speed = hist_batch["agent_feats"][:, -1, :, 0] * 10.0
        feats_proto = hist_batch["agent_feats"][:, -1]
        # agents valid at the last history step stay the slot's live set
        # for the whole future (families keep validity constant in time)
        valid = hist_batch["agent_valid"][:, -1]
        out, out_acts = [], []
        for t in range(t_hist, t_total):
            # span = host dispatch time of the async device step — the
            # number the pipelining argument cares about; no added sync
            with self.obs.span("rollout.step"):
                cache, logits, pose, speed, acts = self._step(
                    self.params, cache, logits, pose, speed, feats_proto,
                    valid, keys, jnp.asarray(t, jnp.int32))
            self.ticks += 1
            self.obs.counter("rollout.ticks").inc()
            out.append(pose)
            out_acts.append(acts)
        # (B, T_fut, A, 3), (B, T_fut, A)
        return jnp.stack(out, axis=1), jnp.stack(out_acts, axis=1)

    def run(self, scenes: Sequence[Dict[str, np.ndarray]], *, t_hist: int,
            n_samples: int, seed: int = 0, t_total: Optional[int] = None):
        """Closed-loop rollouts for every scene x sample.

        ``scenes``: scene tensor dicts (any registered family's layout) or
        ``repro.scenarios.Scene`` objects. Returns sampled future poses,
        shape (n_scenes, n_samples, t_total - t_hist, A, 3), as numpy;
        the matching sampled action ids land in ``self.last_actions``,
        shape (n_scenes, n_samples, t_total - t_hist, A) — the isolation
        suite compares them bit-for-bit against the sim server's.
        """
        scenes = [s.tensors if hasattr(s, "tensors") else s for s in scenes]
        t_total = t_total or self.scen.num_steps
        n_scenes = len(scenes)
        total = n_scenes * n_samples
        # host-side key plumbing: the per-(scene, sample) stream is fixed
        # before any slot/shard assignment, so placement can't change it
        keys_all = np.asarray(
            jax.random.key_data(rollout_keys(seed, n_scenes, n_samples)))

        def lane_hist(flat_idx):
            s = scenes[flat_idx // n_samples]
            return {
                "map_feats": s["map_feats"], "map_pose": s["map_pose"],
                "map_valid": s["map_valid"],
                "agent_feats": s["agent_feats"][:t_hist],
                "agent_pose": s["agent_pose"][:t_hist],
                "agent_valid": s["agent_valid"][:t_hist],
            }

        futures, actions = [], []
        for start in range(0, total, self.num_slots):
            lanes = [min(start + i, total - 1)
                     for i in range(self.num_slots)]  # pad tail by repeating
            hist = {k: jnp.asarray(np.stack([lane_hist(i)[k] for i in lanes]))
                    for k in lane_hist(0)}
            keys = jnp.asarray(keys_all[np.asarray(lanes)])
            with self.obs.span("rollout.chunk"):
                fut, acts = self._run_chunk(hist, keys, t_hist, t_total)
                futures.append(np.asarray(fut[:total - start]))
                actions.append(np.asarray(acts[:total - start]))
        flat = np.concatenate(futures, axis=0)[:total]
        t_fut = t_total - t_hist
        a = self.scen.num_agents
        self.last_actions = np.concatenate(actions, axis=0)[:total] \
            .reshape(n_scenes, n_samples, t_fut, a)
        return flat.reshape(n_scenes, n_samples, t_fut, a, 3)
