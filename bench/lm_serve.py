"""LM serving cells: the program's ``Server`` on a DeepSeek-V2 configuration,
driven on the wall clock.

The configuration file holds the catalog model's ``config.json`` numbers at
its top level and the server's settings under ``serve`` (slots, ``max_len``,
prefill chunk, drain lag, cache dtype). The traffic file holds a fixed list
of (prompt, output) lengths drawn once from its own seed, the sampling
temperature and the arrival process. ``saturated``: one request is always
queued and enters, first come first served, as a slot frees. The run's seed
makes the weights, the prompt token ids (uniform over the vocabulary) and
the sampling noise, never the work.

Set-up compiles both tick programs and fills the slots; the window opens
once every slot holds a prefilled request (and the decode-only program has
run), with nothing outstanding, and closes by landing every tick it sent.
A step is one sequence advancing by one generated token that reached the
host (prompt tokens are not steps); the gap is the time between a
sequence's consecutive tokens reaching the host. After the window the
queued request is cancelled and the server runs on until enough sequences
have finished for the check. ``attempted`` counts the requests finished
by the run's end, ``failed`` those of them with a token count other than
their ``max_new_tokens`` or a non-finite logit. Then the server is freed
and finished sequences drawn from the seed are compared with the plain
reference (``lm_reference``): the reference's logits over each sequence
(prompt, then the served tokens) at every served position, with the
served token's Gumbel noise replayed.

* ``token_gap``: the largest amount by which a served token's reference
  logit (over the temperature) plus its noise lies below the best one;
* ``token_logit_err``: the largest |served logit - reference logit| of the
  served tokens.

The control (``control=True``) is the reference with its matmul operands
rounded to float8_e4m3's 3 mantissa bits in the program's place: the token
its logits pick under the same noise, and its logit of the served token.
"""
from __future__ import annotations

import collections
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import lm_reference as ref
from bench import lm_work

#: longest a run waits after the window for sequences to finish
DRAIN_LIMIT_S = 120.0
#: the server's host spans (``Server``'s latent tick and its children)
SPANS = ("lm_server.",)
#: stored mantissa bits of the control's matmul operands (float8_e4m3)
CONTROL_BITS = 3
#: the program's registered architecture a configuration file runs
ARCH = "deepseek-v2-lite-16b"
#: file key -> the program's ModelConfig field (``.mla`` / ``.moe`` ones
#: with a prefix)
WIDTHS = {"hidden_size": "d_model", "num_attention_heads": "num_q_heads",
          "vocab_size": "vocab_size", "rope_theta": "rope_base",
          "kv_lora_rank": "mla.kv_lora_rank",
          "qk_nope_head_dim": "mla.qk_nope_dim",
          "qk_rope_head_dim": "mla.qk_rope_dim",
          "v_head_dim": "mla.v_head_dim", "q_lora_rank": "mla.q_lora_rank",
          "n_routed_experts": "moe.num_experts",
          "num_experts_per_tok": "moe.top_k",
          "moe_intermediate_size": "moe.expert_ff",
          "n_shared_experts": "moe.num_shared",
          "first_k_dense_replace": "moe.first_k_dense",
          "intermediate_size": "moe.dense_ff",
          "norm_topk_prob": "moe.norm_topk_prob"}


def _field(cfg, name):
    for part in name.split("."):
        cfg = getattr(cfg, part)
    return cfg


def program_config(file):
    """The registered arch at the file's depth; every width, the rope
    scaling and the routing have to match the file."""
    import dataclasses

    from repro.configs import get_config

    cfg = dataclasses.replace(get_config(ARCH),
                              num_layers=file["num_hidden_layers"])
    diff = {k: (_field(cfg, f), file[k]) for k, f in WIDTHS.items()
            if _field(cfg, f) != file[k]}
    rs, frs = cfg.rope_scaling, file["rope_scaling"]
    if (rs.factor, rs.original_max_position, rs.beta_fast, rs.beta_slow,
            rs.mscale, rs.mscale_all_dim) != (
            frs["factor"], frs["original_max_position_embeddings"],
            frs["beta_fast"], frs["beta_slow"], frs["mscale"],
            frs["mscale_all_dim"]):
        diff["rope_scaling"] = (rs, frs)
    if file["routed_scaling_factor"] != 1 or file["scoring_func"] \
            != "softmax" or file["topk_method"] != "greedy":
        diff["routing"] = "the program routes softmax, greedy, scale 1"
    if diff:
        raise ValueError(f"registered {ARCH} differs from its "
                         f"configuration file: {diff}")
    return cfg


def check_params(params, model):
    """The benchmark's weights have the program's parameter layout."""
    from repro.nn.module import abstract_params

    want = abstract_params(model.specs())
    same = jax.tree.structure(want) == jax.tree.structure(params) and \
        all(a.shape == b.shape for a, b in zip(jax.tree.leaves(want),
                                               jax.tree.leaves(params)))
    if not same:
        raise ValueError("the program's parameter layout differs from "
                         "bench/lm_reference.py")


def sampling_seed(seed):
    return int(np.random.default_rng([seed, 4]).integers(0, 2 ** 31 - 1))


def build(cell, model_cfg=None):
    """The program's model and server for this cell, and the weights."""
    from repro import obs
    from repro.nn.transformer import TransformerLM
    from repro.runtime.server import Server

    model = TransformerLM(model_cfg or program_config(cell.config))
    params = ref.make_params(cell.config, cell.seed)
    check_params(params, model)
    sv = cell.config["serve"]
    reg = obs.Registry()
    srv = Server(model, params, num_slots=sv["slots"], max_len=sv["max_len"],
                 cache_dtype=sv["cache_dtype"],
                 prefill_chunk=sv["prefill_chunk"],
                 drain_lag=sv.get("drain_lag", 1), eos_id=cell.traffic["eos"],
                 seed=sampling_seed(cell.seed), registry=reg)
    return srv, reg, params


class Tokens:
    """Host arrival times of every sequence's tokens."""

    def __init__(self, srv):
        self.srv = srv
        self.seen = {}           # uid -> tokens on the host so far
        self.submitted = {}      # uid -> submit time
        self.first = {}          # uid -> first token time
        self.last = {}           # uid -> latest token time
        self.gaps = []           # (end time, gap seconds)
        self.arrived = []        # (time, tokens)
        self.live = set()        # uids live at the last record

    def record(self, now):
        live = self.srv._live
        reqs = list(live.values()) + [self.srv.done[u]
                                      for u in self.live - live.keys()]
        self.live = set(live)
        for r in reqs:
            new = len(r.generated) - self.seen.get(r.uid, 0)
            if new <= 0:
                continue
            self.seen[r.uid] = len(r.generated)
            self.arrived.append((now, new))
            if r.uid in self.last:
                self.gaps.append((now, now - self.last[r.uid]))
            else:
                self.first[r.uid] = now
            self.gaps += [(now, 0.0)] * (new - 1)
            self.last[r.uid] = now


class Traffic:
    """Saturated arrivals over the traffic file's fixed length list."""

    def __init__(self, cell, srv, toks):
        from repro.runtime.server import Request

        self.req_cls, self.cell, self.srv, self.toks = Request, cell, srv, toks
        self.tr = cell.traffic
        self.vocab = cell.config["vocab_size"]
        self.made = 0
        self.open = True

    def request(self, uid):
        p, o = self.tr["lengths"][uid % len(self.tr["lengths"])]
        rng = np.random.default_rng([self.cell.seed, 3, uid])
        return self.req_cls(uid=uid, prompt=rng.integers(
            0, self.vocab, p).astype(np.int32), max_new_tokens=o,
            temperature=self.tr["temperature"])

    def offer(self, now):
        if not self.open:
            return
        while len(self.srv.queue) < self.tr["arrivals"]["queued"]:
            self.srv.submit(self.request(self.made))
            self.toks.submitted[self.made] = now
            self.made += 1


def _filled(srv):
    """Every slot holds a request whose prompt is all fed."""
    return all(s.request is not None
               and s.prefill_pos == len(s.request.prompt)
               for s in srv.slots)


def drive(srv, traffic, toks, *, until, tick_log=None):
    """Tick until ``until(now)`` holds after a tick; returns that time.
    ``tick_log`` (a list) receives each tick's work (``Server.last_work``)."""
    now = time.perf_counter()
    while True:
        traffic.offer(now)
        if not srv.step():
            return now
        now = time.perf_counter()
        toks.record(now)
        if tick_log is not None:
            tick_log.append(srv.last_work)
        if until(now):
            return now


def settle(srv, toks):
    """Land every dispatched tick's tokens, in turn; returns the time."""
    while srv._pending:
        srv._drain(len(srv._pending) - 1)
        toks.record(time.perf_counter())
    jax.block_until_ready((srv.cache, srv.state))
    return time.perf_counter()


def run(cell, model_cfg=None):
    t_setup = time.perf_counter()
    srv, reg, params = build(cell, model_cfg)
    toks = Tokens(srv)
    traffic = Traffic(cell, srv, toks)
    drive(srv, traffic, toks,
          until=lambda now: _filled(srv) and srv.tick_traces >= 2)
    setup_s = time.perf_counter() - t_setup
    traces0 = srv.tick_traces

    seconds = cell.window_seconds()
    tick_log = [] if cell.trace else None
    with cell.profile() as prof:
        settle(srv, toks)
        t0 = prof.mark_start()
        gen0 = reg.counter("lm_server.generated_tokens").value
        drive(srv, traffic, toks, tick_log=tick_log,
              until=lambda now: now - t0 >= seconds)
        t_end = settle(srv, toks)
        gen1 = reg.counter("lm_server.generated_tokens").value
        prof.mark_end()
    window = t_end - t0
    window_traces = srv.tick_traces - traces0

    # close the window: cancel what was never admitted, finish enough
    # sequences for the check
    traffic.open = False
    srv.queue.clear()
    want = cell.traffic["check_sequences"]
    t_drain = time.perf_counter()
    drive(srv, traffic, toks,
          until=lambda now: len(srv.done) >= want
          or now - t_drain > DRAIN_LIMIT_S)
    srv.flush()
    toks.record(time.perf_counter())
    peak = cell.memory_peak()

    sound = {u for u, r in srv.done.items()
             if len(r.generated) == r.max_new_tokens
             and np.isfinite(r.logits).all()}
    steps = sum(n for t, n in toks.arrived if t0 <= t <= t_end)
    gaps = [g for t, g in toks.gaps if t0 <= t <= t_end]
    ttft = [toks.first[u] - s for u, s in toks.submitted.items()
            if u in toks.first and t0 <= toks.first[u] <= t_end]
    metrics = {"rollout_steps_per_s": steps / window,
               "step_gap_p95_ms": 1e3 * float(np.percentile(gaps, 95))}
    layer = _layer_counts(cell, tick_log) if cell.trace else {}
    info = {"slots": srv.num_slots, "window_s": window, "ticks": srv.ticks,
            "window_compiles": window_traces, "steps": steps,
            "generated_counter": gen1 - gen0,
            "prefill_tokens": reg.counter("lm_server.prefill_tokens").value,
            "ttft_p50_ms": 1e3 * float(np.median(ttft)) if ttft else None,
            "ttft_p95_ms": 1e3 * float(np.percentile(ttft, 95))
            if ttft else None,
            "finished": len(srv.done),
            "finished_sound": len(sound),
            "sequences_finished_per_s": sum(
                1 for u in srv.done if t0 <= toks.last.get(u, -1) <= t_end)
            / window}

    sample = _sample(cell, srv)
    max_len = srv.max_len
    del srv, traffic, toks
    gc.collect()
    readings, control = check(cell, params, sample, max_len)
    return {"setup_s": setup_s, "metrics": metrics, "layer": layer,
            "attempted": info["finished"],
            "failed": info["finished"] - len(sound),
            "readings": readings, "control": control,
            "memory_peak_bytes": peak, "info": info}


def _layer_counts(cell, tick_log):
    """Work of the traced window's ticks, for the per-layer readers."""
    cfg, cdt = cell.config, cell.config["serve"]["cache_dtype"]
    acc = collections.Counter()
    for live, (n, start, last) in tick_log:
        f, b = lm_work.mla_decode_work(cfg, live, cdt)
        acc["decode_flops"] += f
        acc["decode_bytes"] += b
        f, b = lm_work.moe_work(cfg, len(live) + n)
        acc["moe_flops"] += f
        acc["moe_bytes"] += b
        acc["model_flops"] += lm_work.tick_model_flops(cfg, live, n, start,
                                                       last)
    return dict(acc, ticks=len(tick_log))


def _sample(cell, srv):
    """Finished sequences drawn from the seed."""
    rng = np.random.default_rng([cell.seed, 2])
    done = sorted(srv.done)
    n = min(cell.traffic["check_sequences"], len(done))
    pick = rng.choice(done, n, replace=False) if n else []
    return [srv.done[int(u)] for u in pick]


def check(cell, params, sample, length, *, operand_bits=CONTROL_BITS):
    """Readings of the served sequences, {name: value}, and the control's
    in the same form (None unless the cell runs the control)."""
    cfg, temp = cell.config, cell.traffic["temperature"]
    seed = sampling_seed(cell.seed)
    gaps, errs, cgaps, cerrs, tokens = [], [], [], [], 0
    for req in sample:
        p, gen = len(req.prompt), np.asarray(req.generated, np.int64)
        seq = np.concatenate([req.prompt, gen[:-1]]).astype(np.int32)
        at = np.arange(p - 1, p - 1 + len(gen))
        with jax.default_matmul_precision("highest"):
            lg = np.asarray(ref.logits(cfg, params, seq, at, length=length))
            lc = np.asarray(ref.logits(cfg, params, seq, at, length=length,
                                       operand_bits=operand_bits)) \
                if cell.control else None
        noise = np.asarray(ref.gumbel_noise(
            jnp.int32(seed), jnp.int32(req.uid),
            jnp.arange(p, p + len(gen), dtype=jnp.int32), lg.shape[1]))
        pert = (lg / temp + noise) if temp > 0 else lg
        rows = np.arange(len(gen))
        gaps.append(pert.max(-1) - pert[rows, gen])
        errs.append(np.abs(np.asarray(req.logits) - lg[rows, gen]))
        tokens += len(gen)
        if lc is not None:
            pick = np.argmax((lc / temp + noise) if temp > 0 else lc, -1)
            cgaps.append(pert.max(-1) - pert[rows, pick])
            cerrs.append(np.abs(lc[rows, gen] - lg[rows, gen]))

    def widest(xs):
        return float(max((x.max(initial=0.0) for x in xs),
                         default=float("inf")))

    out = {"token_gap": widest(gaps), "token_logit_err": widest(errs),
           "tokens_compared": tokens}
    if not cell.control:
        return out, None
    return out, {"token_gap": widest(cgaps), "token_logit_err": widest(cerrs),
                 "tokens_compared": tokens}
