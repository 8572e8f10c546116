"""DeepSeek-V2 at a tiny size on the CPU against the plain reference
(``bench/lm_reference.py``, which imports nothing from ``repro``), on
seeded random weights: the model with and without its latent cache, the
serving tick, dropless routing, the grouped-head decode kernel, and the
YaRN and routing numbers of the published configuration.

The tiny size keeps the architecture's shape: 3 layers (the first dense),
8 routed experts of which 2 a token plus 1 shared, a 32-wide latent and a
16-wide rope key. The program runs in float32 here, so it agrees with the
float32 reference to float32 rounding; each tolerance says why it is what
it is.
"""
import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import lm_reference as ref  # noqa: E402
from bench import lm_serve  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core.encodings import (rope_frequencies, yarn_frequencies,  # noqa: E402
                                  yarn_ramp_bounds)
from repro.kernels import flash_decode as fd  # noqa: E402
from repro.nn.moe import MoE  # noqa: E402
from repro.nn.transformer import TransformerLM  # noqa: E402
from repro.runtime.server import Request, Server  # noqa: E402

ARCH = "deepseek-v2-lite-16b"
#: float32 program against the float32 reference: the two sum in different
#: orders (absorbed vs expanded attention, grouped vs dense experts), a few
#: ulps of logits of size ~4 through 3 layers
TOL = 1e-4


def tiny_config():
    cfg = get_config(ARCH).reduced(dtype="float32")
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, num_shared=1))


def file_of(cfg):
    """The configuration-file (``config.json``) form of a ModelConfig."""
    m, e, rs = cfg.mla, cfg.moe, cfg.rope_scaling
    return {"hidden_size": cfg.d_model, "num_attention_heads": cfg.num_q_heads,
            "vocab_size": cfg.vocab_size, "num_hidden_layers": cfg.num_layers,
            "kv_lora_rank": m.kv_lora_rank, "qk_nope_head_dim": m.qk_nope_dim,
            "qk_rope_head_dim": m.qk_rope_dim, "v_head_dim": m.v_head_dim,
            "q_lora_rank": m.q_lora_rank, "n_routed_experts": e.num_experts,
            "num_experts_per_tok": e.top_k,
            "moe_intermediate_size": e.expert_ff,
            "n_shared_experts": e.num_shared,
            "first_k_dense_replace": e.first_k_dense,
            "intermediate_size": e.dense_ff, "norm_topk_prob": e.norm_topk_prob,
            "rope_theta": cfg.rope_base, "rms_norm_eps": 1e-6,
            "rope_scaling": {
                "type": "yarn", "factor": rs.factor,
                "original_max_position_embeddings": rs.original_max_position,
                "beta_fast": rs.beta_fast, "beta_slow": rs.beta_slow,
                "mscale": rs.mscale, "mscale_all_dim": rs.mscale_all_dim},
            "routed_scaling_factor": 1, "scoring_func": "softmax",
            "topk_method": "greedy"}


@pytest.fixture(scope="module")
def tiny():
    cfg = tiny_config()
    file = file_of(cfg)
    model = TransformerLM(cfg)
    params = jax.tree.map(lambda a: a.astype(jnp.float32),
                          ref.make_params(file, 11))
    lm_serve.check_params(params, model)
    return cfg, file, model, params


def _reference(file, params, tokens, length=64):
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.logits(file, params, tokens,
                                     np.arange(len(tokens)), length=length))


# -- the published numbers ------------------------------------------------------

def test_yarn_numbers_of_the_published_configuration():
    cfg = get_config(ARCH)
    attn = TransformerLM(dataclasses.replace(cfg, num_layers=2)) \
        .groups[0][0].attention
    # 192 ** -0.5 * (0.1 * 0.707 * ln 40 + 1) ** 2
    assert attn.scale == pytest.approx(0.114721, abs=5e-7)
    assert yarn_ramp_bounds(64, 10000.0, 4096, 32.0, 1.0) == (10, 23)
    f = yarn_frequencies(64, 10000.0, 40.0, 4096, 32.0, 1.0)
    orig = rope_frequencies(32, 10000.0)
    np.testing.assert_allclose(f[:11], orig[:11], rtol=1e-12)
    np.testing.assert_allclose(f[23:], orig[23:] / 40.0, rtol=1e-12)
    assert np.all((f[11:23] < orig[11:23]) & (f[11:23] > orig[11:23] / 40))
    # the reference derives the same numbers from the file's keys
    rf, rscale = ref.yarn(file_of(cfg))
    np.testing.assert_allclose(rf, f, rtol=1e-12)
    assert rscale == pytest.approx(attn.scale, rel=1e-12)
    assert cfg.moe.norm_topk_prob is False


def test_routing_weights_are_not_renormalised():
    """norm_topk_prob false: each token's k weights are the softmax over all
    experts taken at the top k (they sum to less than 1); true keeps the
    renormalised softmax of the other registered MoE archs."""
    rng = np.random.default_rng(0)
    d, e, f, k = 16, 8, 8, 2
    x = jnp.asarray(rng.normal(size=(1, 5, d)), jnp.float32)
    params = {"router": jnp.asarray(rng.normal(size=(d, e)), jnp.float32),
              **{n: jnp.asarray(rng.normal(size=s) / 4, jnp.float32)
                 for n, s in (("gate", (e, d, f)), ("up", (e, d, f)),
                              ("down", (e, f, d)))}}
    probs = jax.nn.softmax(x[0] @ params["router"], -1)
    top, ids = jax.lax.top_k(probs, k)

    def dense(weights):
        h = jnp.einsum("td,edf->etf", x[0], params["gate"])
        u = jnp.einsum("td,edf->etf", x[0], params["up"])
        eo = jnp.einsum("etf,efd->etd", jax.nn.silu(h) * u, params["down"])
        w = jnp.zeros((5, e)).at[jnp.arange(5)[:, None], ids].set(weights)
        return jnp.einsum("te,etd->td", w, eo)

    assert float(jnp.max(jnp.sum(top, -1))) < 1.0
    for norm, weights in ((False, top),
                          (True, top / jnp.sum(top, -1, keepdims=True))):
        moe = MoE(d_model=d, num_experts=e, top_k=k, expert_ff=f,
                  norm_topk_prob=norm)
        with jax.default_matmul_precision("highest"):
            y, _ = moe(params, x)
            want = dense(weights)
        # float32 sums in a different order: ulps of outputs of size ~1
        np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                                   atol=1e-5)


def test_dropless_moe_keeps_every_token_on_one_expert():
    """Every token routed to expert 0 (first of its two): the capacity path
    would drop all but a few of them; the dropless one computes them all,
    as the dense sum over the routed experts does."""
    rng = np.random.default_rng(1)
    t, d, e, f, k = 64, 16, 8, 8, 2
    moe = MoE(d_model=d, num_experts=e, top_k=k, expert_ff=f,
              norm_topk_prob=False)
    router = np.zeros((d, e), np.float32)
    router[:, 0] = 50.0                      # x > 0 everywhere: expert 0 wins
    router[:, 1] = 10.0
    x = jnp.asarray(np.abs(rng.normal(size=(1, t, d))) + 0.1, jnp.float32)
    params = {"router": jnp.asarray(router),
              **{n: jnp.asarray(rng.normal(size=s) / 4, jnp.float32)
                 for n, s in (("gate", (e, d, f)), ("up", (e, d, f)),
                              ("down", (e, f, d)))}}
    assert moe.capacity(t) < t               # the capacity path would drop
    with jax.default_matmul_precision("highest"):
        y, _ = moe(params, x)
        probs = jax.nn.softmax(x[0] @ params["router"], -1)
        out = jnp.zeros((t, d))
        for j in (0, 1):
            h = x[0] @ params["gate"][j]
            u = x[0] @ params["up"][j]
            out = out + probs[:, j:j + 1] * (
                (jax.nn.silu(h) * u) @ params["down"][j])
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(out), atol=1e-5)
    assert float(jnp.min(jnp.abs(y))) > 0.0


# -- the grouped-head, latent decode kernel -----------------------------------

@pytest.mark.parametrize("latent", [False, True])
def test_flash_decode_folds_grouped_heads(latent):
    """group = Hq / Hkv > 1 folded into the query rows (interpret mode)
    against the XLA ragged decode, on a layer-stacked feature-major cache;
    ``latent``: the values are the keys' first ``v_dim`` features."""
    rng = np.random.default_rng(2)
    nl, b, hq, hkv, d, dv, s = 2, 3, 8, 1 if latent else 2, 40, 32, 256
    q = jnp.asarray(rng.normal(size=(b, hq, 1, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(nl, b, hkv, d, s)), jnp.float32)
    v = None if latent else jnp.asarray(
        rng.normal(size=(nl, b, hkv, dv, s)), jnp.float32)
    kvl = jnp.asarray([1, 130, 256], jnp.int32)
    kw = dict(layer=1, scale=0.3, v_dim=dv if latent else None)
    got = fd.flash_decode(q, k, v, kvl, block_k=128, interpret=True, **kw)
    want = fd.decode_ragged_xla(q, k, v, kvl, block_k=128, **kw)
    assert got.shape == (b, hq, 1, dv)
    # both accumulate the same blocks in float32 in the same order
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


# -- the model and the server against the reference ------------------------------

def test_model_prefill_then_decode_matches_the_reference(tiny):
    """A 30-token prefill through the latent cache, then 10 single-token
    decodes (absorbed, through the ragged decode), against the reference's
    full forward pass; and the model without a cache."""
    cfg, file, model, params = tiny
    toks = np.random.default_rng(3).integers(0, cfg.vocab_size, 40) \
        .astype(np.int32)
    want = _reference(file, params, toks)
    with jax.default_matmul_precision("highest"):
        full, _, _ = model(params, jnp.asarray(toks)[None], remat=False)
        cache = model.init_cache(1, 64, jnp.float32)
        lg, _, cache = model(params, jnp.asarray(toks[:30])[None],
                             cache=cache, cache_index=0, remat=False)
        outs = [np.asarray(lg[0])]
        for i in range(30, 40):
            lg, _, cache = model(params, jnp.asarray(toks[i:i + 1])[None],
                                 cache=cache, cache_index=i, remat=False)
            outs.append(np.asarray(lg[0]))
    np.testing.assert_allclose(np.asarray(full[0]), want, atol=TOL)
    np.testing.assert_allclose(np.concatenate(outs), want, atol=TOL)


def test_server_tick_matches_the_reference(tiny):
    """More requests than slots through the latent tick: prompts fed in
    8-token chunks beside decoding slots, sampled on the device at
    temperature 1 (Gumbel keyed by uid and position) and greedily, two
    ticks dispatched ahead. Every served token's logit is the reference's,
    and the served token is the reference's choice under the same noise."""
    cfg, file, model, params = tiny
    srv = Server(model, params, num_slots=3, max_len=64, prefill_chunk=8,
                 drain_lag=2, seed=5)
    rng = np.random.default_rng(4)
    for uid in range(7):
        srv.submit(Request(uid=uid, prompt=rng.integers(
            0, cfg.vocab_size, rng.integers(3, 30)).astype(np.int32),
            max_new_tokens=int(rng.integers(1, 9)),
            temperature=1.0 if uid % 2 else 0.0))
    done = srv.run_until_drained()
    assert sorted(done) == list(range(7))
    for uid, req in done.items():
        p, gen = len(req.prompt), np.asarray(req.generated)
        assert len(gen) == req.max_new_tokens
        lg = _reference(file, params,
                        np.concatenate([req.prompt, gen[:-1]]))[p - 1:]
        rows = np.arange(len(gen))
        np.testing.assert_allclose(req.logits, lg[rows, gen], atol=TOL)
        if req.temperature > 0:
            lg = lg + np.asarray(ref.gumbel_noise(
                jnp.int32(5), jnp.int32(uid),
                jnp.arange(p, p + len(gen), dtype=jnp.int32), lg.shape[1]))
        np.testing.assert_array_equal(lg.argmax(-1), gen)


def test_server_eos_stops_a_latent_request_early(tiny):
    cfg, _, model, params = tiny
    prompt = np.arange(5, 17, dtype=np.int32)
    probe = Server(model, params, num_slots=1, max_len=64, prefill_chunk=8)
    probe.submit(Request(uid=0, prompt=prompt, max_new_tokens=8))
    ref_tokens = probe.run_until_drained()[0].generated
    eos = ref_tokens[2]
    first = ref_tokens.index(eos)
    srv = Server(model, params, num_slots=1, max_len=64, prefill_chunk=8,
                 eos_id=eos, drain_lag=3)
    srv.submit(Request(uid=0, prompt=prompt, max_new_tokens=8))
    srv.submit(Request(uid=1, prompt=prompt, max_new_tokens=2))
    done = srv.run_until_drained()
    assert done[0].generated == ref_tokens[:first + 1]
    assert done[1].generated == ref_tokens[:min(2, first + 1)]


def test_the_serving_runner_reads_the_tiny_cell(tiny):
    """The benchmark's runner (``bench/lm_serve.py``) on the tiny model:
    a window, its steps, and the check's readings against the reference,
    tight for the program and wider for the float8-operand control."""
    from bench import run

    cfg, file, _, _ = tiny
    config = dict(file, serve={"slots": 3, "max_len": 64, "prefill_chunk": 8,
                               "drain_lag": 2, "cache_dtype": "float32"})
    traffic = {"kind": "lm_serve", "lengths": [[20, 6], [9, 4], [33, 9],
                                               [5, 3], [12, 7]],
               "temperature": 1.0, "eos": None, "check_sequences": 3,
               "arrivals": {"process": "saturated", "queued": 1}}
    cell = run.Cell("tiny-lm", config, traffic,
                    {"token_gap": 1e-3, "token_logit_err": 1e-3},
                    seed=2 ** 31 + 9, seconds=0.5, trace=False, control=True)
    out = lm_serve.run(cell, model_cfg=cfg)
    assert out["metrics"]["rollout_steps_per_s"] > 0
    assert out["failed"] == 0 and out["attempted"] >= 3
    r, c = out["readings"], out["control"]
    assert r["tokens_compared"] == c["tokens_compared"] > 0
    assert run.correct_of(r, cell.limits)
    # 3 significand bits move the logits by far more than float32 rounding
    assert c["token_logit_err"] > 100 * r["token_logit_err"]


# -- the benchmark cell's files and readers --------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _bench_json(*parts):
    import json

    with open(os.path.join(ROOT, "bench", *parts)) as f:
        return json.load(f)


def test_the_cell_lengths_are_those_the_traffic_seed_draws():
    """The traffic file's fixed (prompt, output) list is what its own seed
    draws, log-uniform over its ranges: the run's seed never changes it."""
    tr = _bench_json("traffic", "lm_long_prompt_saturated.json")
    lengths = np.asarray(tr["lengths"])
    rng = np.random.default_rng(tr["lengths_seed"])
    for col, (lo, hi) in enumerate((tr["prompt_range"],
                                    tr["output_range"])):
        want = np.round(np.exp(rng.uniform(np.log(lo), np.log(hi),
                                           len(lengths))))
        np.testing.assert_array_equal(lengths[:, col], want)


def test_the_registered_arch_is_the_configuration_file():
    """The cell runs the registered arch at the file's depth; a width the
    file and the program disagree on is refused before any run."""
    file = _bench_json("configs", "deepseek-v2-lite.json")
    cfg = lm_serve.program_config(file)
    assert cfg.num_layers == file["num_hidden_layers"] == 6
    assert file["reduced_from"] == {"num_hidden_layers": 27}
    for key, value in (("moe_intermediate_size", 1024),
                       ("norm_topk_prob", True)):
        with pytest.raises(ValueError, match=key):
            lm_serve.program_config(dict(file, **{key: value}))


def test_the_tick_work_counts_of_the_cell():
    """Shape counts the rooflines divide by device time: a decode-only tick
    of 64 slots at 5,000 live rows, and the experts' weights of 5 layers."""
    from bench import lm_work

    file = _bench_json("configs", "deepseek-v2-lite.json")
    f, b = lm_work.mla_decode_work(file, [5000] * 64)
    assert b == 64 * 5000 * 576 * 2 * 6
    assert f == 2 * 16 * 64 * 5000 * (576 + 512) * 6
    f, b = lm_work.moe_work(file, 64)
    assert f == 2 * 3 * 2048 * 1408 * 64 * 6 * 5
    assert b == (553_648_128 + 2 * 64 * 6 * 2048) * 2 * 5


def _reader(name):
    import importlib.util

    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("lm_metric", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


LM_READERS = ("mla_decode_roofline.lm", "moe_roofline.lm", "lm_tick_mfu",
              "prefill_share.lm", "device_idle_share.lm", "tick_host_ms.lm")


def test_the_lm_readers_on_a_hand_made_trace():
    """Each new per-layer metric read from a reduced trace whose op names,
    scopes and spans are those of the latent tick; on a trace without
    them (a program without the latent tick) each returns None."""
    peak = {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}
    work = {"decode_flops": 1e9, "decode_bytes": 819e6,      # 1 ms
            "moe_flops": 1e9, "moe_bytes": 1638e6,           # 2 ms
            "model_flops": 197e9, "ticks": 2}
    trace = {
        "busy_s": 0.008, "window_s": 0.01, "devices": 1,
        "op_seconds": {
            "%flash_decode_latent.3 = (f32[...]) custom-call(...)": 0.002,
            "%ragged-dot-none.14 = f32[384,1408] custom-call(...)": 0.003,
            "%fusion.7 = bf16[...] fusion(...)": 0.003},
        "scope_seconds": {"moe.experts": 0.001, "lm_server.prefill": 0.002},
        "spans": [("lm_server.tick", 0.0, 0.004),
                  ("lm_server.drain", 0.001, 0.003),
                  ("lm_server.tick", 0.005, 0.008),
                  ("lm_server.drain", 0.006, 0.007)]}
    ctx = {"trace": trace, "work": work, "peak": peak}
    got = {n: _reader(n)(ctx) for n in LM_READERS}
    assert got == pytest.approx({
        "mla_decode_roofline.lm": 50.0, "moe_roofline.lm": 50.0,
        "lm_tick_mfu": 10.0, "prefill_share.lm": 25.0,
        "device_idle_share.lm": 20.0, "tick_host_ms.lm": 2.0})
    bare = {"trace": dict(trace, op_seconds={"%fusion.1 = f32[] add()": 0.1},
                          scope_seconds={}, spans=[]),
            "work": {}, "peak": peak}
    assert {n: _reader(n)(bare) for n in LM_READERS
            if n != "device_idle_share.lm"} == dict.fromkeys(
                set(LM_READERS) - {"device_idle_share.lm"})
