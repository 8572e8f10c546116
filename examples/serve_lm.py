"""Serve a small LM with continuous batching (per-slot cache cursors).

Run:  PYTHONPATH=src python examples/serve_lm.py
(uses the reduced same-family config so it runs on CPU; ``--full`` on real
hardware). The default, DeepSeek-V2-Lite, takes the latent-cache tick:
prompts enter in chunks beside decoding slots, tokens are sampled on the
device, and ticks are dispatched ahead of the host. ``--arch`` names any
registered LM (``phi4-mini-3.8b`` takes the one-token-a-tick path).
"""
import argparse
import logging
import time

import numpy as np

import jax

from repro.configs import get_config
from repro.nn import module as nnm
from repro.nn.transformer import build_model
from repro.runtime.server import Request, Server


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-v2-lite-16b")
    ap.add_argument("--full", action="store_true",
                    help="full config (needs a real accelerator)")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--slots", type=int, default=3)
    args = ap.parse_args()
    logging.basicConfig(level=logging.INFO)
    log = logging.getLogger("serve_lm")

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced(dtype="float32")
    model = build_model(cfg)
    params = nnm.init_params(model.specs(), jax.random.key(0))
    srv = Server(model, params, num_slots=args.slots, max_len=128,
                 prefill_chunk=8, drain_lag=2)

    rng = np.random.default_rng(0)
    t0 = time.time()
    for uid in range(args.requests):
        srv.submit(Request(uid=uid,
                           prompt=rng.integers(1, cfg.vocab_size,
                                               rng.integers(3, 10)),
                           max_new_tokens=int(rng.integers(4, 12)),
                           temperature=0.7))
    done = srv.run_until_drained()
    dt = time.time() - t0
    toks = sum(len(r.generated) for r in done.values())
    log.info("%d requests, %d tokens, %.2fs (%.1f tok/s), %d ticks",
             len(done), toks, dt, toks / dt, srv.ticks)
    for uid in sorted(done):
        r = done[uid]
        log.info("req %d: prompt=%s -> %s", uid, list(r.prompt), r.generated)


if __name__ == "__main__":
    main()
