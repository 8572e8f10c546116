"""Continuous-batching simulation service: mid-flight scene admission.

The closed-loop analogue of :class:`repro.runtime.server.Server`, built
on the same fixed-slot discipline the :class:`RolloutEngine` introduced —
but where the engine runs one batch of scenes start-to-finish in
lockstep, the server is **long-lived**: scenes are admitted into free
slots and evicted at their horizon *while every other slot keeps
ticking*, so heavy traffic streams through one resident jitted tick with
exactly one compilation. The moving parts:

* **Slab KV cache.** All concurrent scenes share ONE layer-stacked,
  feature-major ``(L, B, H, c, S_slab)`` cache (f32 / bf16 / int8 +
  scales) instead of each scene paying its own ``max_len``
  allocation + compile. A retiring scene frees its slot immediately; the
  successor's rows simply overwrite the prefix. Rows the predecessor
  left beyond the reset cursor are **not scrubbed** — they are provably
  unreachable, because every decode masks key positions >=
  ``kv_length = cursor + n`` and the cursor only ever advances over
  freshly written rows (``docs/serving.md`` states the full argument;
  ``tests/test_sim_server.py`` pins it bit-for-bit, adversarially).

* **Incremental prefill through the shared tick.** Admission writes only
  the scene's M map tokens (``AgentSimModel.admit_map`` on a throwaway
  1-slot cache, installed via ``install_slot_rows``); the scene's
  history then streams through the SAME jitted tick as everyone else,
  one teacher-forced step per tick — the sim twin of the LM server's
  token-by-token prompt prefill. No head-of-line blocking: a slot
  mid-prefill coexists with slots mid-rollout, and eviction is legal at
  any tick (mid-prefill included).

* **Bit-reproducibility under churn.** Sampling is keyed per
  (scene_id, sample_id) exactly like ``rollout_keys`` and folded with
  the slot's own sim time, and the streamed prefill is bit-identical to
  the engine's one-shot prefill (fully masked key blocks contribute
  exact zeros to the online softmax), so a scene's actions and poses are
  bit-identical to the same scene run alone in a fresh
  ``RolloutEngine`` — regardless of arrival order, slot assignment,
  co-residents, or cache recycling.

* **Host<->device pipelining.** ``tick()`` only *dispatches* device
  work; per-tick outputs (poses, action ids) are kept as device handles
  on a drain queue and materialized ``drain_lag`` ticks later, so tick
  t+1 is enqueued while tick t's metrics drain.

* **Per-slot health / quarantine.** The drain already materializes
  every lane's poses and action ids on the host; a numerically poisoned
  lane (NaN state — a bad scene, a kernel bug, a flipped bit) is caught
  there by a cheap non-finite / action-range check and **quarantined**:
  the lane's ``SimResult`` is delivered immediately with
  ``status="failed"`` + a reason, its slot is scrubbed back to the
  fresh-cache invariant and freed, and ``sim_server.quarantined`` /
  a ``sim_server.quarantine`` event record it. Healthy co-resident
  slots keep serving BIT-identical outputs to a fault-free run — slots
  only ever read their own slab rows, and every kernel applies masks
  with ``jnp.where`` after the score computation, so even non-finite
  stale rows cannot leak (drilled by ``repro.launch.chaos`` and pinned
  in ``tests/test_chaos.py``).
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Any, Deque, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.nn.agent_sim import install_slot_rows
from repro.runtime.rollout import step_kinematics
from repro.scenarios.core import ScenarioConfig

__all__ = ["SceneRequest", "SimResult", "SimServer", "serve_scenes",
           "poisson_drive"]


@dataclasses.dataclass
class SceneRequest:
    """One (scene, sample) rollout lane.

    ``tensors`` is a scene tensor dict (or a ``Scene`` — anything with a
    ``.tensors``). ``t_hist`` history steps are teacher-forced, then the
    lane rolls out closed-loop until step ``t_total`` (default: the
    scenario config's ``num_steps``). Neither affects tensor shapes, so
    requests with different lengths share the one compiled tick.

    The sampling key is ``fold_in(fold_in(key(seed), scene_id),
    sample_id)`` — the exact ``rollout_keys`` stream, so a lane with
    ``scene_id=i, sample_id=k`` reproduces lane (i, k) of a
    ``RolloutEngine.run(..., seed=seed)`` bit-for-bit. ``scene_id``
    defaults to ``uid``.
    """
    uid: int
    tensors: Any
    t_hist: int
    t_total: Optional[int] = None
    seed: int = 0
    scene_id: Optional[int] = None
    sample_id: int = 0

    def __post_init__(self):
        if hasattr(self.tensors, "tensors"):
            self.tensors = self.tensors.tensors
        if self.scene_id is None:
            self.scene_id = self.uid


@dataclasses.dataclass
class SimResult:
    uid: int
    t_hist: int
    t_total: int
    future: np.ndarray        # (t_total - t_hist, A, 3) sampled poses
    actions: np.ndarray       # (t_total - t_hist, A) sampled action ids
    # slot-health outcome: "ok", or "failed" when the lane was
    # quarantined (non-finite poses / out-of-range actions) — the
    # partial future/actions up to the failure are preserved for
    # debugging, zero-filled beyond it
    status: str = "ok"
    reason: str = ""


@dataclasses.dataclass
class _Slot:
    req: Optional[SceneRequest] = None
    t: int = 0                # next sim step this slot will process


class SimServer:
    """Long-lived continuous-batching closed-loop simulation service."""

    def __init__(self, model, params, scen_cfg: ScenarioConfig, *,
                 num_slots: int, max_len: Optional[int] = None,
                 cache_dtype=None, decode_impl: Optional[str] = None,
                 drain_lag: int = 1,
                 registry: Optional[obs.Registry] = None):
        """``max_len``: slab width per slot in cache rows (default: the
        config's worst case ``M + num_steps * A``; rounded up to the
        decode kernel's 128-row block like ``RolloutEngine``). A request
        needs ``M + t_total * A <= max_len``. ``drain_lag``: how many
        ticks a tick's outputs stay on device before the host
        materializes them (1 = classic double buffering; 0 = synchronous,
        for latency measurements). ``cache_dtype`` / ``decode_impl`` as
        in ``RolloutEngine``.

        ``registry``: telemetry home (``repro.obs``; ``None`` = process
        default, ``obs.NULL`` = off). Every working tick records a
        ``sim_server.tick`` span (its children: see ``tick``) plus
        occupancy / resident / queued gauges from host-side
        bookkeeping; admissions record
        ``sim_server.queue_wait.seconds`` (submit -> admit) and, once a
        lane's first closed-loop action drains,
        ``sim_server.first_action.seconds`` (admit -> first action on
        host, pipelined drain included). All samples are host wall-clock
        or host counters — telemetry never touches a device value, so
        obs-on/obs-off runs are bit-identical and compile-count-identical
        (tests/test_obs.py)."""
        self.obs = registry if registry is not None else obs.get_registry()
        self.model = model
        self.params = params
        self.scen = scen_cfg
        self.num_slots = num_slots
        self.cache_dtype = cache_dtype
        self.decode_impl = decode_impl
        self.drain_lag = drain_lag
        max_len = max_len or (scen_cfg.num_map
                              + scen_cfg.num_steps * scen_cfg.num_agents)
        self.max_len = -(-max_len // 128) * 128 if max_len > 128 else max_len
        m = scen_cfg.num_map
        # throwaway admission cache: just wide enough for the map block,
        # block-aligned the same way as the slab
        self._sub_len = -(-m // 128) * 128 if m > 128 else m
        self._accel = jnp.asarray(scen_cfg.accel_values(), jnp.float32)
        self._yaw = jnp.asarray(scen_cfg.yaw_values(), jnp.float32)

        self.cache = model.init_cache(num_slots, self.max_len, cache_dtype)
        a = scen_cfg.num_agents
        kd = jax.random.key_data(jax.random.key(0))
        cdt = model.cfg.compute_dtype
        self.state = {
            "logits": jnp.zeros((num_slots, a, model.cfg.num_actions), cdt),
            "pose": jnp.zeros((num_slots, a, 3), jnp.float32),
            "speed": jnp.zeros((num_slots, a), jnp.float32),
            "proto": jnp.zeros((num_slots, a, scen_cfg.agent_feat_dim),
                               jnp.float32),
            "valid": jnp.zeros((num_slots, a), bool),
            "keys": jnp.zeros((num_slots,) + kd.shape, kd.dtype),
        }
        self.slots = [_Slot() for _ in range(num_slots)]
        self.queue: Deque[SceneRequest] = collections.deque()
        self.done: Dict[int, SimResult] = {}
        self._buf: Dict[int, Dict[str, Any]] = {}       # uid -> fill state
        # drain queue: (routes, acts_dev, pose_dev); routes maps batch
        # row -> (uid, future index)
        self._pending: Deque[Tuple[List[Tuple[int, int, int]], Any, Any]] \
            = collections.deque()
        self.ticks = 0
        self.admitted = 0
        self.evicted = 0
        self.quarantined = 0
        self._num_actions = int(model.cfg.num_actions)
        # Tracing the impl body is what a (re)compilation costs; the
        # retrace-guard test pins these at exactly 1 under slot churn.
        # Mirrored into the registry (sim_server.tick_traces /
        # admit_traces counters) so obs_report shows compile counts.
        self.tick_traces = 0
        self.admit_traces = 0
        self._submit_ts: Dict[int, float] = {}      # uid -> submit wall-time
        self.obs.gauge("sim_server.slab_rows").set(num_slots * self.max_len)
        self.obs.gauge("sim_server.slab_bytes").set(
            sum(int(np.prod(v.shape)) * v.dtype.itemsize
                for v in jax.tree.leaves(self.cache)))
        # CostAccounted AOT-compiles on first call (one trace, one
        # compilation — the retrace guards still hold) and records the
        # compiled FLOPs/bytes as cost.* gauges; see repro/obs/cost.py.
        self._tick = obs.CostAccounted(
            jax.jit(self._tick_impl, donate_argnums=(1, 2)),
            "sim_server.tick", registry=self.obs)
        self._admit = obs.CostAccounted(
            jax.jit(self._admit_impl, donate_argnums=(1, 2)),
            "sim_server.admit", registry=self.obs)

    # -- admission / eviction -------------------------------------------------

    def submit(self, req: SceneRequest):
        req.t_total = req.t_total or self.scen.num_steps
        live = self.scen.num_map + req.t_total * self.scen.num_agents
        if live > self.max_len:
            raise ValueError(
                f"request {req.uid}: live length {live} rows exceeds the "
                f"slab width {self.max_len}; raise max_len or shorten "
                f"t_total")
        if not 0 < req.t_hist <= req.t_total:
            raise ValueError(
                f"request {req.uid}: need 0 < t_hist <= t_total, got "
                f"({req.t_hist}, {req.t_total})")
        if req.uid in self._buf or req.uid in self.done \
                or any(s.req is not None and s.req.uid == req.uid
                       for s in self.slots) \
                or any(r.uid == req.uid for r in self.queue):
            raise ValueError(f"duplicate request uid {req.uid}")
        self._submit_ts[req.uid] = time.perf_counter()
        self.obs.counter("sim_server.submitted").inc()
        self.queue.append(req)

    def evict(self, uid: int) -> bool:
        """Cancel a resident request (legal at any tick, mid-prefill
        included). Its slot is immediately reusable; whatever rows it
        wrote stay in the slab, unreachable to successors. Returns
        whether the uid was found (resident or queued)."""
        for slot in self.slots:
            if slot.req is not None and slot.req.uid == uid:
                slot.req = None
                self._buf.pop(uid, None)
                self.evicted += 1
                self.obs.counter("sim_server.evicted").inc()
                self.obs.event("sim_server.evict", uid=uid, phase="resident")
                return True
        for r in self.queue:
            if r.uid == uid:
                self.queue.remove(r)
                self._submit_ts.pop(uid, None)
                self.obs.event("sim_server.evict", uid=uid, phase="queued")
                return True
        return False

    def _admit_pending(self):
        for si, slot in enumerate(self.slots):
            if slot.req is not None or not self.queue:
                continue
            req = self.queue.popleft()
            now = time.perf_counter()
            submit_ts = self._submit_ts.pop(req.uid, now)
            self.obs.histogram("sim_server.queue_wait.seconds") \
                .record(now - submit_ts)
            key = jax.random.fold_in(
                jax.random.fold_in(jax.random.key(req.seed), req.scene_id),
                req.sample_id)
            tt = req.tensors
            with self.obs.span("sim_server.admit",
                               args={"uid": req.uid, "slot": si}):
                self.cache, self.state = self._admit(
                    self.params, self.cache, self.state,
                    jnp.asarray(tt["map_feats"])[None],
                    jnp.asarray(tt["map_pose"])[None],
                    jnp.asarray(tt["map_valid"])[None],
                    jnp.asarray(si, jnp.int32), jax.random.key_data(key))
            slot.req = req
            slot.t = 0
            t_fut = req.t_total - req.t_hist
            a = self.scen.num_agents
            self._buf[req.uid] = {
                "future": np.zeros((t_fut, a, 3), np.float32),
                "actions": np.zeros((t_fut, a), np.int32),
                "filled": 0, "req": req,
                "admit_ts": time.perf_counter(),
            }
            self.admitted += 1
            self.obs.counter("sim_server.admitted").inc()

    def _admit_impl(self, params, cache, state, map_feats, map_pose,
                    map_valid, si, key_data):
        """Jitted admission: cursor reset + re-arm + map-token install.

        ``si`` is traced, so every slot shares one compilation. The map
        rows are computed on a fresh throwaway 1-slot cache — admission
        is byte-equivalent to the first M rows of a fresh engine's
        prefill by construction — then installed over slot ``si``'s
        prefix. Slot state (pose/speed/logits/validity) is zeroed; the
        first teacher tick supplies the real values.
        """
        self.admit_traces += 1
        self.obs.counter("sim_server.admit_traces").inc()
        with jax.named_scope("sim_server.admit"):
            m = map_feats.shape[1]
            sub = self.model.init_cache(1, self._sub_len, self.cache_dtype)
            _, sub = self.model.admit_map(params, sub, map_feats, map_pose,
                                          map_valid, impl=self.decode_impl)
            cache = install_slot_rows(cache, sub, si, m)
            state = dict(state)
            for k in ("logits", "pose", "speed", "proto", "valid"):
                state[k] = state[k].at[si].set(
                    jnp.zeros(state[k].shape[1:], state[k].dtype))
            state["keys"] = state["keys"].at[si].set(key_data)
            return cache, state

    # -- the tick -------------------------------------------------------------

    def _tick_impl(self, params, cache, state, tfeats, tpose, tvalid,
                   t, active, teacher):
        """One service tick, fully on device, every slot in one call.

        Rollout slots run the exact ``RolloutEngine`` step: sample an
        action per agent from the previous step's logits (key folded
        with the slot's OWN sim time — slots at different progress draw
        from their own streams), integrate kinematics, decode the new
        agent tokens against the slab. Teacher (mid-prefill) slots feed
        their history step instead — same token path, same mask, so
        prefill is just ticks with overridden inputs. Inactive slots are
        carried along shape-stably: their sampled garbage is discarded,
        their state frozen, and their cursor un-advanced — the A rows
        the decode scattered into their slab prefix land beyond the
        authoritative cursor and are unreachable (deliberately so: churn
        actively scribbles retired slots, and the isolation tests prove
        it cannot matter).
        """
        self.tick_traces += 1
        self.obs.counter("sim_server.tick_traces").inc()
        with jax.named_scope("sim_server.tick"):
            return self._tick_body(params, cache, state, tfeats, tpose,
                                   tvalid, t, active, teacher)

    def _tick_body(self, params, cache, state, tfeats, tpose, tvalid,
                   t, active, teacher):
        logits, pose, speed = state["logits"], state["pose"], state["speed"]
        proto, valid = state["proto"], state["valid"]
        with jax.named_scope("sim_server.sample"):
            keys = jax.random.wrap_key_data(state["keys"])
            keys_t = jax.vmap(jax.random.fold_in)(keys, t)
            acts = jax.vmap(jax.random.categorical)(
                keys_t, logits.astype(jnp.float32))          # (B, A)
        with jax.named_scope("sim_server.kinematics"):
            ai, yi = jnp.divmod(acts, self.scen.yaw_bins)
            new_pose, new_speed = step_kinematics(
                pose, speed, self._accel[ai], self._yaw[yi])
            new_pose = jnp.where(valid[..., None], new_pose, pose)
            new_speed = jnp.where(valid, new_speed, speed)
        tm = teacher[:, None]
        pose_in = jnp.where(tm[..., None], tpose, new_pose)
        speed_in = jnp.where(tm, tfeats[..., 0] * 10.0, new_speed)
        valid_in = jnp.where(tm, tvalid, valid)
        proto_in = jnp.where(tm[..., None], tfeats, proto)
        feats_in = jnp.where(tm[..., None], tfeats,
                             proto.at[..., 0].set(new_speed / 10.0))
        cur0 = cache["cursor"]
        new_logits, cache = self.model.step(params, cache, feats_in, pose_in,
                                            valid_in, t,
                                            impl=self.decode_impl)
        am1, am2 = active[:, None], active[:, None, None]
        cache["cursor"] = jnp.where(active, cache["cursor"], cur0)
        state = {
            "logits": jnp.where(am2, new_logits, logits),
            "pose": jnp.where(am2, pose_in, pose),
            "speed": jnp.where(am1, speed_in, speed),
            "proto": jnp.where(am2, proto_in, proto),
            "valid": jnp.where(am1, valid_in, valid),
            "keys": state["keys"],
        }
        return cache, state, acts, pose_in

    def tick(self) -> bool:
        """Admit, advance every resident slot one sim step, retire.

        Returns False when there was nothing to do (no resident or
        queued work). The device call is dispatched asynchronously;
        outputs are materialized ``drain_lag`` ticks later.

        A working tick is one ``sim_server.tick`` span whose children
        mark the runtime's layers: ``sim_server.admit`` (one a lane),
        ``assemble`` (host inputs and their transfers), ``dispatch`` (the
        jitted tick call) and ``drain`` (where the host waits for an
        earlier tick's outputs).
        """
        # idle polls are free and would swamp the latency histogram with
        # near-zero samples; only working ticks open a span
        if not self.queue and all(s.req is None for s in self.slots):
            return False
        with self.obs.span("sim_server.tick"):
            self._tick_host()
        return True

    def _tick_host(self):
        self._admit_pending()
        b, a = self.num_slots, self.scen.num_agents
        with self.obs.span("sim_server.assemble"):
            active = np.zeros(b, bool)
            teacher = np.zeros(b, bool)
            t_vec = np.zeros(b, np.int32)
            tfeats = np.zeros((b, a, self.scen.agent_feat_dim), np.float32)
            tpose = np.zeros((b, a, 3), np.float32)
            tvalid = np.zeros((b, a), bool)
            routes: List[Tuple[int, int, int]] = []
            for si, slot in enumerate(self.slots):
                req = slot.req
                if req is None:
                    continue
                active[si] = True
                t_vec[si] = slot.t
                if slot.t < req.t_hist:
                    teacher[si] = True
                    tt = req.tensors
                    tfeats[si] = tt["agent_feats"][slot.t]
                    tpose[si] = tt["agent_pose"][slot.t]
                    tvalid[si] = tt["agent_valid"][slot.t]
                else:
                    routes.append((si, req.uid, slot.t - req.t_hist))
            inputs = (jnp.asarray(tfeats), jnp.asarray(tpose),
                      jnp.asarray(tvalid), jnp.asarray(t_vec),
                      jnp.asarray(active), jnp.asarray(teacher))
        with self.obs.span("sim_server.dispatch"):
            self.cache, self.state, acts, pose = self._tick(
                self.params, self.cache, self.state, *inputs)
        self.ticks += 1
        if routes:
            self._pending.append((routes, acts, pose))
        for slot in self.slots:
            if slot.req is None:
                continue
            slot.t += 1
            if slot.t >= slot.req.t_total:      # horizon: retire, free slot
                slot.req = None
        with self.obs.span("sim_server.drain"):
            self._drain(self.drain_lag)
        if self.obs.enabled:
            m = self.scen.num_map
            live = sum(min(m + s.t * a, self.max_len)
                       for s in self.slots if s.req is not None)
            self.obs.counter("sim_server.ticks").inc()
            self.obs.gauge("sim_server.live_rows").set(live)
            self.obs.gauge("sim_server.occupancy").set(
                live / float(self.num_slots * self.max_len))
            self.obs.gauge("sim_server.resident").set(
                sum(s.req is not None for s in self.slots))
            self.obs.gauge("sim_server.queued").set(len(self.queue))

    # -- slot health / quarantine ---------------------------------------------

    def _health_reason(self, acts_row: np.ndarray,
                       pose_row: np.ndarray) -> Optional[str]:
        """Cheap host-side check on outputs the drain already
        materialized (no extra device touch): a numerically poisoned
        lane shows up as non-finite poses (NaN state propagates through
        the kinematic integration) or action ids outside the model's
        action space (categorical over non-finite logits)."""
        if not np.isfinite(pose_row).all():
            return "nonfinite_pose"
        if acts_row.min() < 0 or acts_row.max() >= self._num_actions:
            return "action_out_of_range"
        return None

    def _scrub_slot(self, si: int):
        """Reset slot ``si``'s slab rows and carried state to the fresh-
        cache values. Stale rows are unreachable even when non-finite
        (every kernel applies its mask with ``jnp.where`` AFTER the
        score computation, so a NaN score at a masked position is
        replaced, never propagated) — the scrub is defense in depth: it
        restores the fresh-cache invariant for the next tenant and stops
        the quarantined slot's frozen NaN state from writing more
        non-finite rows on subsequent (inactive, discarded) ticks."""
        cache = dict(self.cache)
        for k in ("k", "v", "k_scale", "v_scale"):
            if k in cache:
                cache[k] = cache[k].at[:, si].set(0)
        cache["times"] = cache["times"].at[si].set(0)
        cache["seg"] = cache["seg"].at[si].set(-1)
        cache["cursor"] = cache["cursor"].at[si].set(0)
        self.cache = cache
        state = dict(self.state)
        for k in ("logits", "pose", "speed", "proto"):
            state[k] = state[k].at[si].set(0)
        state["valid"] = state["valid"].at[si].set(False)
        self.state = state

    def _quarantine(self, si: int, uid: int, reason: str):
        """Evict a poisoned lane: its result is delivered immediately as
        ``failed`` (partial outputs preserved), its slot is scrubbed and
        freed for the next admission, and the event is counted — healthy
        slots are untouched and stay bit-identical to a fault-free run
        (pinned by tests/test_chaos.py)."""
        buf = self._buf.pop(uid, None)
        if buf is not None:
            req = buf["req"]
            self.done[uid] = SimResult(
                uid=uid, t_hist=req.t_hist, t_total=req.t_total,
                future=buf["future"], actions=buf["actions"],
                status="failed", reason=reason)
        slot = self.slots[si]
        if slot.req is not None and slot.req.uid == uid:
            slot.req = None
            self._scrub_slot(si)
        self.quarantined += 1
        self.obs.counter("sim_server.quarantined").inc()
        self.obs.event("sim_server.quarantine", uid=uid, slot=si,
                       reason=reason)

    # -- draining -------------------------------------------------------------

    def _drain(self, keep: int):
        """Materialize all but the newest ``keep`` ticks' outputs,
        health-checking every routed lane on the way."""
        while len(self._pending) > keep:
            routes, acts_dev, pose_dev = self._pending.popleft()
            acts_np = np.asarray(acts_dev)
            pose_np = np.asarray(pose_dev)
            for si, uid, fi in routes:
                buf = self._buf.get(uid)
                if buf is None:                 # evicted mid-flight
                    continue
                reason = self._health_reason(acts_np[si], pose_np[si])
                if reason is not None:
                    self._quarantine(si, uid, reason)
                    continue
                if buf["filled"] == 0:          # lane's first action landed
                    self.obs.histogram("sim_server.first_action.seconds") \
                        .record(time.perf_counter() - buf["admit_ts"])
                buf["future"][fi] = pose_np[si]
                buf["actions"][fi] = acts_np[si]
                buf["filled"] += 1
                req = buf["req"]
                if buf["filled"] == req.t_total - req.t_hist:
                    self.done[uid] = SimResult(
                        uid=uid, t_hist=req.t_hist, t_total=req.t_total,
                        future=buf["future"], actions=buf["actions"])
                    del self._buf[uid]

    def flush(self):
        """Drain every outstanding tick output to the host."""
        self._drain(0)

    def run_until_drained(self, max_ticks: int = 100_000
                          ) -> Dict[int, SimResult]:
        while (self.queue or any(s.req for s in self.slots)) \
                and self.ticks < max_ticks:
            self.tick()
        self.flush()
        return self.done

    # -- accounting -----------------------------------------------------------

    def stats(self) -> Dict[str, float]:
        """Slab accounting + lifecycle counters (host-side; no sync)."""
        slab_bytes = sum(int(np.prod(v.shape)) * v.dtype.itemsize
                         for v in jax.tree.leaves(self.cache))
        m, a = self.scen.num_map, self.scen.num_agents
        live = sum(min(m + s.t * a, self.max_len)
                   for s in self.slots if s.req is not None)
        return {
            "slots": float(self.num_slots),
            "slab_rows": float(self.num_slots * self.max_len),
            "slab_mib": slab_bytes / 2 ** 20,
            "live_rows": float(live),
            "occupancy": live / float(self.num_slots * self.max_len),
            "resident": float(sum(s.req is not None for s in self.slots)),
            "queued": float(len(self.queue)),
            "ticks": float(self.ticks),
            "admitted": float(self.admitted),
            "evicted": float(self.evicted),
            "quarantined": float(self.quarantined),
            "tick_compilations": float(self.tick_traces),
            "admit_compilations": float(self.admit_traces),
        }

    def postmortem_state(self) -> Dict[str, Any]:
        """Per-slot phase/cursor/scene-id table plus queue/drain state —
        pure host bookkeeping (no device touch), packaged for the flight
        recorder (``repro.obs.FlightRecorder``)."""
        m, a = self.scen.num_map, self.scen.num_agents
        slots = []
        for si, slot in enumerate(self.slots):
            if slot.req is None:
                slots.append({"slot": si, "phase": "idle"})
                continue
            req = slot.req
            buf = self._buf.get(req.uid, {})
            slots.append({
                "slot": si, "uid": req.uid, "scene_id": req.scene_id,
                "sample_id": req.sample_id, "t": slot.t,
                "t_hist": req.t_hist, "t_total": req.t_total,
                "phase": "prefill" if slot.t < req.t_hist else "rollout",
                "cursor_rows": min(m + slot.t * a, self.max_len),
                "filled": int(buf.get("filled", 0)),
            })
        return {"slots": slots,
                "queued_uids": [r.uid for r in self.queue],
                "done_uids": sorted(self.done),
                "pending_drains": len(self._pending),
                "stats": self.stats()}

    def dump_postmortem(self, path: str, *, reason: str = "manual",
                        **context) -> str:
        """Write a flight-recorder bundle (registry tail + snapshot +
        the per-slot table above) to ``path``; returns the path. Works
        even with telemetry off — the slot table is always live."""
        fr = obs.FlightRecorder(self.obs)
        fr.add_provider("sim_server", self.postmortem_state)
        return fr.dump(reason=reason, path=path, **context)


def poisson_drive(server: SimServer, requests: Sequence[SceneRequest], *,
                  rate: float, seed: int = 0,
                  warmup_ticks: int = 0) -> Dict[str, Any]:
    """Drive ``server`` with ``requests`` arriving as a Poisson process.

    ``rate`` is the mean arrival rate in requests per *tick* (the
    service clock): inter-arrival gaps are drawn i.i.d. exponential with
    mean ``1/rate``, so admissions interleave arbitrarily with resident
    scenes mid-prefill and mid-rollout — the schedule the invariance
    tests randomize over. Ticks until every request has drained.

    Per-tick wall-clock (device dispatch + pipelined drain) lands in a
    standalone :class:`repro.obs.Histogram` — the same log-bucket
    sketch the telemetry registry uses, so every consumer reads
    percentiles off one implementation instead of keeping raw lists.
    The first ``warmup_ticks`` *working* ticks (compile + warmup) are
    skipped. Returns ``{"latency": Histogram, "ticks": total working
    ticks incl. warmup, "arrival_ticks": ...}``.
    """
    rng = np.random.default_rng(seed)
    t_arrive = np.cumsum(rng.exponential(1.0 / rate, len(requests)))
    pending = collections.deque(zip(t_arrive, requests))
    hist = obs.Histogram("poisson_drive.tick.seconds")
    ticked_n = 0
    clock = 0.0
    while pending or server.queue or any(s.req for s in server.slots):
        while pending and pending[0][0] <= clock:
            server.submit(pending.popleft()[1])
        t0 = time.perf_counter()
        ticked = server.tick()
        if ticked:
            if ticked_n >= warmup_ticks:
                hist.record(time.perf_counter() - t0)
            ticked_n += 1
        clock += 1.0
        if not ticked and pending:        # idle gap: jump to next arrival
            clock = max(clock, pending[0][0])
    server.flush()
    return {"latency": hist, "ticks": ticked_n,
            "arrival_ticks": t_arrive.tolist()}


def serve_scenes(server: SimServer, scenes: Sequence, *, t_hist: int,
                 n_samples: int, seed: int = 0,
                 t_total: Optional[int] = None) -> np.ndarray:
    """Engine-shaped convenience: push ``scenes x n_samples`` lanes
    through ``server`` and return futures shaped exactly like
    ``RolloutEngine.run`` — (n_scenes, n_samples, T_fut, A, 3) — keyed so
    lane (si, ki) reproduces the engine's lane (si, ki) bit-for-bit.
    ``server`` must be idle (no resident work) and is left idle."""
    assert not server.queue and not any(s.req for s in server.slots), \
        "serve_scenes needs an idle server"
    base = len(server.done)
    uid0 = (max(server.done) + 1) if server.done else 0
    lanes = []
    for si, scene in enumerate(scenes):
        for ki in range(n_samples):
            uid = uid0 + len(lanes)
            server.submit(SceneRequest(
                uid=uid, tensors=scene, t_hist=t_hist, t_total=t_total,
                seed=seed, scene_id=si, sample_id=ki))
            lanes.append(uid)
    done = server.run_until_drained()
    assert len(done) - base == len(lanes)
    failed = [uid for uid in lanes if done[uid].status != "ok"]
    if failed:
        raise RuntimeError(
            f"serve_scenes: lanes {failed} were quarantined "
            f"({', '.join(sorted({done[u].reason for u in failed}))}); "
            "the stacked futures would silently contain failed lanes")
    fut = np.stack([done[uid].future for uid in lanes])
    t_fut = fut.shape[1]
    return fut.reshape(len(scenes), n_samples, t_fut,
                       server.scen.num_agents, 3)
