"""Batched LM serving loop: continuous batching over fixed decode slots.

``num_slots`` decode lanes share jitted, shape-stable device calls, with
per-slot cache cursors; retired slots are re-admitted at once (their stale
rows are unreachable behind the new request's cursor). Two tick programs,
picked by the model's cache:

* **Latent cache** (MLA models: the stacked ``(L, B, 1, r + dr, S)``
  buffer of ``TransformerLM.init_cache``). One jitted tick advances every
  decoding slot by one token *and* feeds one prefill chunk of up to
  ``prefill_chunk`` prompt tokens for one admitting slot, so the experts'
  weights are read once a layer for both (``TransformerLM.latent_forward``).
  A tick with no chunk to feed runs the decode-only program. Sampling is on
  the device: temperature, then Gumbel-max with noise keyed by (seed,
  request uid, token position), greedy at temperature 0; only each
  sampled token id and its own logit go to the host. The device keeps each
  slot's last token, position and tokens left, so ticks are dispatched
  ``drain_lag`` ahead of the one whose tokens the host waits for. With
  ``eos_id=None`` every request produces exactly ``max_new_tokens``.
  A working tick is one ``lm_server.tick`` span with the children
  ``lm_server.admit``, ``.assemble``, ``.dispatch`` and ``.drain``; its
  device ops carry the named scopes ``lm_server.prefill`` (the chunk's own
  ops), ``lm.mla_decode``, ``moe.experts`` and ``lm_server.sample``. The
  registry counts ``lm_server.generated_tokens`` (as they reach the host)
  and ``lm_server.prefill_tokens`` (as they are dispatched).
* **Other caches** (per-layer ``Attention`` / SSM caches): every active
  slot advances one token a tick, prompt tokens included, and the logits
  come to the host, which samples (greedy or temperature).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.nn.attention import LATENT_DECODE_BLOCK


@dataclasses.dataclass
class Request:
    uid: int
    prompt: np.ndarray              # (prompt_len,) int32
    max_new_tokens: int = 16
    temperature: float = 0.0
    generated: Optional[List[int]] = None
    #: the program's logit of each generated token (latent-cache path)
    logits: Optional[List[float]] = None

    @property
    def text_len(self) -> int:
        return len(self.prompt) + len(self.generated or ())


@dataclasses.dataclass
class _Slot:
    request: Optional[Request] = None
    cursor: int = 0                 # tokens written into this slot's cache
    prefill_pos: int = 0            # next prompt token to feed
    left: int = 0                   # tokens still to dispatch (latent path)


def sample_tokens(logits, uid, pos, temperature, *, seed: int,
                  vocab_size: int):
    """On-device sampling of one token a row: logits (R, V), uid / pos (R,)
    int32 (the request and the position of the token being sampled),
    temperature (R,) float32. Returns (token ids (R,) int32, each token's
    own logit (R,) float32).

    Temperature > 0: argmax of logits / temperature plus Gumbel noise drawn
    with ``jax.random.gumbel(fold_in(fold_in(key(seed), uid), pos), (V,))``,
    which is what a reference has to draw to replay the choice; 0: argmax
    of the logits. Columns at or past ``vocab_size`` (padding) never win.
    """
    logits = logits.astype(jnp.float32)
    col = jnp.arange(logits.shape[-1])
    logits = jnp.where(col < vocab_size, logits, -jnp.inf)
    base = jax.random.key(seed)

    def noise(u, p):
        k = jax.random.fold_in(jax.random.fold_in(base, u), p)
        return jax.random.gumbel(k, (logits.shape[-1],), jnp.float32)

    g = jax.vmap(noise)(uid, pos)
    t = temperature[:, None]
    score = jnp.where(t > 0, logits / jnp.maximum(t, 1e-6) + g, logits)
    tok = jnp.argmax(score, axis=-1).astype(jnp.int32)
    return tok, jnp.take_along_axis(logits, tok[:, None], -1)[:, 0]


def init_latent_state(num_slots: int):
    """The latent tick's per-slot device state: the token to feed next, its
    position, tokens still to produce, whether the slot decodes, its
    request's uid and sampling temperature."""
    b = num_slots
    return {"tok": jnp.zeros((b,), jnp.int32),
            "pos": jnp.zeros((b,), jnp.int32),
            "left": jnp.zeros((b,), jnp.int32),
            "active": jnp.zeros((b,), bool),
            "uid": jnp.zeros((b,), jnp.int32),
            "temp": jnp.zeros((b,), jnp.float32)}


def latent_tick(model, params, cache, state, chunk_tok, meta, chunk_temp, *,
                with_chunk: bool, seed: int, eos_id: Optional[int]):
    """One latent-cache tick: decode every active slot one token and,
    ``with_chunk``, feed the prefill chunk ``chunk_tok`` (C,) described by
    ``meta`` = [slot, start, valid tokens, last chunk, uid, max_new] into
    its slot. Returns (cache, state, token ids (B + 1,), their logits
    (B + 1,)); row B is the chunk's first generated token (last chunk
    only)."""
    b = state["tok"].shape[0]
    act = state["active"]
    parts = [dict(tokens=state["tok"][:, None], cursor=state["pos"],
                  kv_length=jnp.where(act, state["pos"] + 1, 0))]
    slot, start, n, last, uid, max_new = (meta[i] for i in range(6))
    if with_chunk:
        parts.append(dict(tokens=chunk_tok[None], cursor=start[None],
                          kv_length=(start + n)[None], slots=slot[None],
                          scope="lm_server.prefill"))
    xs, cache = model.latent_forward(params, cache, parts)
    h = xs[0][:, 0]
    if with_chunk:
        with jax.named_scope("lm_server.prefill"):
            h_last = jax.lax.dynamic_slice_in_dim(xs[1][0], n - 1, 1, 0)
        h = jnp.concatenate([h, h_last], 0)
    else:
        h = jnp.concatenate([h, h[:1]], 0)
    logits = model.head(params, h)
    with jax.named_scope("lm_server.sample"):
        uids = jnp.concatenate([state["uid"], uid[None]])
        pos = jnp.concatenate([state["pos"] + 1, (start + n)[None]])
        temp = jnp.concatenate([state["temp"], chunk_temp[None]])
        tok, tok_logit = sample_tokens(logits, uids, pos, temp, seed=seed,
                                       vocab_size=model.cfg.vocab_size)
        stop = jnp.zeros((b + 1,), bool) if eos_id is None \
            else tok == eos_id
        left = jnp.where(act, state["left"] - 1, state["left"])
        new = {"tok": jnp.where(act, tok[:b], state["tok"]),
               "pos": jnp.where(act, state["pos"] + 1, state["pos"]),
               "left": left,
               "active": act & (left > 0) & ~stop[:b],
               "uid": state["uid"], "temp": state["temp"]}
        if with_chunk:
            on = (jnp.arange(b) == slot) & (last > 0)
            new = {"tok": jnp.where(on, tok[b], new["tok"]),
                   "pos": jnp.where(on, start + n, new["pos"]),
                   "left": jnp.where(on, max_new - 1, new["left"]),
                   "active": jnp.where(on, (max_new > 1) & ~stop[b],
                                       new["active"]),
                   "uid": jnp.where(on, uid, new["uid"]),
                   "temp": jnp.where(on, chunk_temp, new["temp"])}
    return cache, new, tok, tok_logit


class Server:
    def __init__(self, model, params, *, num_slots: int, max_len: int,
                 eos_id: Optional[int] = None, seed: int = 0,
                 cache_dtype=jnp.float32, prefill_chunk: int = 512,
                 drain_lag: int = 1,
                 registry: Optional[obs.Registry] = None):
        """``cache_dtype``: K/V cache storage dtype — a jnp dtype or
        "float32" / "bfloat16" / "int8" (int8 carries per-row scales and
        dequantizes inside the decode kernel, see ``Attention.init_cache``;
        the latent cache takes float32 or bfloat16). ``prefill_chunk``,
        ``drain_lag``: the latent path's prompt tokens a tick and ticks
        dispatched ahead (module docstring); ``registry``: telemetry home
        (None: the process default).
        """
        self.model = model
        self.params = params
        self.num_slots = num_slots
        self.eos_id = eos_id
        self.seed = seed
        self.rng = np.random.default_rng(seed)
        self.obs = registry if registry is not None else obs.get_registry()
        cfg = getattr(model, "cfg", None)
        self.latent = getattr(cfg, "attention_kind", None) == "mla" \
            and not getattr(cfg, "enc_dec", False)
        if self.latent:
            blk = LATENT_DECODE_BLOCK        # the decode kernel's key block
            if max_len > blk:
                max_len = -(-max_len // blk) * blk
        self.max_len = max_len
        self.cache = model.init_cache(num_slots, max_len, cache_dtype)
        self.slots = [_Slot() for _ in range(num_slots)]
        self.queue: Deque[Request] = collections.deque()
        self.done: Dict[int, Request] = {}
        self.ticks = 0
        if self.latent:
            self.chunk = min(prefill_chunk, max_len)
            self.drain_lag = drain_lag
            self.state = init_latent_state(num_slots)
            self._prefilling: Optional[int] = None    # slot being prefilled
            # (rows: [(row, slot, uid)], token ids, logits) per tick
            self._pending: Deque[Tuple[List[Tuple[int, int, int]], object,
                                       object]] = collections.deque()
            self._live: Dict[int, Request] = {}       # uid -> request
            self.tick_traces = 0
            self._tick = {
                flag: jax.jit(lambda p, c, s, t, m, tp, _f=flag:
                              self._tick_impl(p, c, s, t, m, tp, _f),
                              donate_argnums=(1, 2))
                for flag in (False, True)}
        else:
            self._decode = jax.jit(self._decode_impl)

    # -- admission -----------------------------------------------------------
    def submit(self, request: Request):
        request.generated = []
        request.logits = []
        if self.latent:
            n = len(request.prompt)
            fed = -(-n // self.chunk) * self.chunk
            if n < 1 or request.max_new_tokens < 1 \
                    or n + request.max_new_tokens > self.max_len \
                    or fed > self.max_len:
                raise ValueError(
                    f"request {request.uid}: prompt {n} + {request.max_new_tokens}"
                    f" new tokens (chunks to {fed}) do not fit max_len "
                    f"{self.max_len}")
        self.queue.append(request)

    def _admit(self):
        for slot in self.slots:
            if slot.request is None and self.queue:
                slot.request = self.queue.popleft()
                slot.cursor = 0
                slot.prefill_pos = 0

    # -- main loop -----------------------------------------------------------
    def step(self):
        """One tick: admit, advance every active slot one token, retire."""
        if self.latent:
            return self._latent_step()
        self._admit()
        tokens = np.zeros((self.num_slots, 1), np.int32)
        index = np.zeros(self.num_slots, np.int32)
        active = []
        for i, slot in enumerate(self.slots):
            req = slot.request
            if req is None:
                continue
            active.append(i)
            index[i] = slot.cursor
            if slot.prefill_pos < len(req.prompt):
                tokens[i, 0] = req.prompt[slot.prefill_pos]
            else:
                tokens[i, 0] = req.generated[-1]
        if not active:
            return
        logits, self.cache = self._decode(self.params, self.cache,
                                          jnp.asarray(tokens),
                                          jnp.asarray(index))
        logits = np.asarray(logits.astype(jnp.float32))
        self.ticks += 1
        for i in active:
            slot = self.slots[i]
            req = slot.request
            slot.cursor += 1
            if slot.prefill_pos < len(req.prompt):
                slot.prefill_pos += 1
                if slot.prefill_pos < len(req.prompt):
                    continue                      # still prefilling
            tok = self._sample(logits[i], req)
            req.generated.append(tok)
            finished = (len(req.generated) >= req.max_new_tokens
                        or (self.eos_id is not None and tok == self.eos_id)
                        or slot.cursor >= self.max_len - 1)
            if finished:
                self.done[req.uid] = req
                slot.request = None

    def _decode_impl(self, params, cache, tokens, index_vec):
        logits, _, new_cache = self.model(params, tokens, cache=cache,
                                          cache_index=index_vec, remat=False)
        return logits[:, -1], new_cache

    def _sample(self, logits: np.ndarray, req: Request) -> int:
        if req.temperature <= 0:
            return int(np.argmax(logits))
        p = np.exp((logits - logits.max()) / req.temperature)
        p /= p.sum()
        return int(self.rng.choice(len(p), p=p))

    # -- the latent-cache tick -------------------------------------------------
    def _tick_impl(self, params, cache, state, chunk_tok, meta, chunk_temp,
                   with_chunk: bool):
        self.tick_traces += 1
        return latent_tick(self.model, params, cache, state, chunk_tok, meta,
                           chunk_temp, with_chunk=with_chunk, seed=self.seed,
                           eos_id=self.eos_id)

    def _latent_step(self):
        if not self.queue and all(s.request is None for s in self.slots):
            return False
        with self.obs.span("lm_server.tick"):
            self._latent_tick()
        return True

    def _latent_tick(self):
        b = self.num_slots
        with self.obs.span("lm_server.admit"):
            if self._prefilling is None and self.queue:
                for si, slot in enumerate(self.slots):
                    if slot.request is None:
                        slot.request = self.queue.popleft()
                        slot.prefill_pos = 0
                        slot.left = 0
                        self._live[slot.request.uid] = slot.request
                        self._prefilling = si
                        break
        with self.obs.span("lm_server.assemble"):
            rows = [(si, si, s.request.uid) for si, s in enumerate(self.slots)
                    if s.request is not None and s.left > 0]
            chunk = np.zeros((self.chunk,), np.int32)
            meta = np.zeros((6,), np.int32)
            temp = np.zeros((), np.float32)
            ps = self._prefilling
            if ps is not None:
                slot = self.slots[ps]
                req = slot.request
                lo = slot.prefill_pos
                piece = req.prompt[lo:lo + self.chunk]
                chunk[:len(piece)] = piece
                last = lo + len(piece) == len(req.prompt)
                meta[:] = (ps, lo, len(piece), last, req.uid,
                           req.max_new_tokens)
                temp = np.float32(req.temperature)
                if last:
                    rows.append((b, ps, req.uid))
            inputs = (jnp.asarray(chunk), jnp.asarray(meta),
                      jnp.asarray(temp))
            # host bookkeeping of the tick's work: each decoding slot's
            # live rows after its new row, and the chunk (tokens, first
            # position, whether it ends the prompt)
            self.last_work = (
                [len(self.slots[si].request.prompt)
                 + self.slots[si].request.max_new_tokens - self.slots[si].left
                 for _, si, _ in rows if si != ps],
                (int(meta[2]), int(meta[1]), bool(meta[3])))
        with self.obs.span("lm_server.dispatch"):
            self.cache, self.state, tok, logit = self._tick[ps is not None](
                self.params, self.cache, self.state, *inputs)
        self.ticks += 1
        for _, si, _ in rows:
            if si != ps:
                self.slots[si].left -= 1
        if ps is not None:
            slot = self.slots[ps]
            n = int(meta[2])
            slot.prefill_pos += n
            self.obs.counter("lm_server.prefill_tokens").inc(n)
            if meta[3]:
                slot.left = slot.request.max_new_tokens - 1
                self._prefilling = None
        for slot in self.slots:          # fixed lengths: free at once
            if slot.request is not None and slot.left == 0 \
                    and slot.prefill_pos == len(slot.request.prompt) \
                    and self.eos_id is None:
                slot.request = None
        self._pending.append((rows, tok, logit))
        with self.obs.span("lm_server.drain"):
            self._drain(self.drain_lag)

    def _drain(self, keep: int):
        """Bring all but the newest ``keep`` ticks' tokens to the host."""
        while len(self._pending) > keep:
            rows, tok_dev, logit_dev = self._pending.popleft()
            tok, logit = np.asarray(tok_dev), np.asarray(logit_dev)
            got = 0
            for row, si, uid in rows:
                req = self._live.get(uid)
                if req is None:                 # finished at an earlier eos
                    continue
                t = int(tok[row])
                req.generated.append(t)
                req.logits.append(float(logit[row]))
                got += 1
                eos = self.eos_id is not None and t == self.eos_id
                if eos or len(req.generated) >= req.max_new_tokens:
                    self.done[uid] = self._live.pop(uid)
                    slot = self.slots[si]
                    if slot.request is req:     # an eos frees the slot
                        slot.request = None
                        slot.left = 0
            if got:
                self.obs.counter("lm_server.generated_tokens").inc(got)

    def flush(self):
        """Bring every outstanding tick's tokens to the host."""
        if self.latent:
            self._drain(0)

    def run_until_drained(self, max_ticks: int = 10_000):
        while (self.queue or any(s.request for s in self.slots)) \
                and self.ticks < max_ticks:
            self.step()
        self.flush()
        return self.done
