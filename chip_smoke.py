"""Smoke run of the agent-sim main path on a TPU: ``python chip_smoke.py``.

Drives the registered ``sim-se2-fourier`` model at its full width (256
model dims, 6 layers, 8 heads x 24, Fourier terms 12 -> 200-wide cached
k/v rows; scenes of 48 map tokens + 12 agents x 24 steps), with random
weights made from ``--seed``, through the entry points a user calls:

* ``kernels``: the Pallas kernels at the model's widths against the jnp
  reference (``impl="ref"`` at highest matmul precision) and against their
  XLA path at default precision (``xla`` decode, ``chunked`` attention):
  ragged decode on a layer-stacked f32 and int8 cache, flash forward and
  its gradients;
* ``serve/<cache dtype>``: a 16-slot ``SimServer`` driven by
  ``poisson_drive`` with 32 generated scenes, once with an f32 and once
  with an int8 cache. Every scene drains with finite trajectories, and the
  tick and the admission each compile exactly once;
* ``train``: the fault-tolerant ``Trainer`` running the BC step for 20
  steps at batch 8. The loss is finite and falls.

Each phase prints one line: the device kind, its compile seconds,
``peak_bytes_in_use`` and how many Pallas kernels (``tpu_custom_call``) its
compiled programs contain; the script asserts there are some, which is what
shows the kernels ran and not an XLA fallback.

``--chips 4`` runs only what exists across chips, on four: the scene-sharded
``RolloutEngine`` against the one-device engine (bit-identical per scene),
and one sharded BC train step against the one-device step (loss, each
parameter's gradient, and the updated parameters), followed by the
trainer's closed-loop eval hook on the sharded parameters.

The script needs a TPU and fails without one; there is no CPU fallback. Its
last line is ``{"ok": true, "device": {...}}``. The compilation cache lives
where ``JAX_COMPILATION_CACHE_DIR`` says, else in ``<checkout>/.jax_cache``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "sim-se2-fourier"
SLOTS = 16
SCENES = 32
T_HIST = 12
TRAIN_STEPS = 20
TRAIN_BATCH = 8
#: kernel-vs-reference bound on O(1) attention outputs and gradients: a
#: tiling or layout fault gives O(1) errors, matmul rounding far less
PARITY_TOL = dict(atol=5e-2, rtol=5e-2)
#: kernel-vs-XLA-path bound, both at default matmul precision: twice the
#: largest kernel error against the highest-precision reference read on a
#: TPU v5e (1.03e-2, flash dq)
TWIN_TOL = dict(atol=2e-2, rtol=2e-2)
#: sharded vs one-device BC step: the largest gradient error of a leaf,
#: relative to the leaf's largest gradient (a wrong partition is O(1)),
#: and the updated parameters where Adam's step direction agrees (a sign
#: flip is 2 lr = 3e-3)
GRAD_TOL = 1e-2
PARAM_TOL = 1e-5


def _phase_line(name, device, *, compile_s, kernel_calls, **extra):
    rec = {"phase": name, "device_kind": device.device_kind,
           "compile_s": compile_s,
           "peak_bytes_in_use": device.memory_stats()["peak_bytes_in_use"],
           "kernel_calls": kernel_calls, **extra}
    print(json.dumps(rec), flush=True)


def check(ok, what):
    """Fail the run (also under ``python -O``, which drops asserts)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _gauge(reg, name, path):
    return reg.gauge(name, path=path).value


def _build(seed):
    import jax

    from repro.configs import get_sim_arch
    from repro.nn import module as nnm
    from repro.nn.agent_sim import AgentSimModel

    arch = get_sim_arch(ARCH)
    model = AgentSimModel(arch.agent_sim_config())
    params = nnm.init_params(model.specs(), jax.random.key(seed))
    return arch, model, params


def _kernels(compiled) -> int:
    """Pallas kernels in a compiled program; fails the run if there are
    none (the program would run an XLA fallback)."""
    from repro import obs

    n = int(obs.compiled_cost(compiled).get("kernel_calls", 0))
    check(n > 0, "no Pallas kernel in the compiled program")
    return n


def _aot(fn, *args):
    """Compile ``fn`` for ``args``; returns (compiled, seconds, kernels)."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    secs = time.perf_counter() - t0
    return compiled, secs, _kernels(compiled)


def kernels_phase(device, model, arch, seed):
    """Pallas kernels at the model's widths against the jnp reference."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref
    from repro.kernels.flash_decode import quantize_kv

    rng = np.random.default_rng(seed)
    scen = arch.scenario_config()
    c = model.attn.cache_dims[0]                    # 200-wide cached rows
    h, a = arch.num_heads, scen.num_agents
    s = scen.num_map + scen.num_steps * a           # 336 scene tokens
    slab = -(-s // 128) * 128                       # the server's 384
    layers, b = 2, 8
    normal = lambda *shape: jnp.asarray(rng.normal(size=shape), jnp.float32)
    # scene token times: map rows 0, agents at step t at t + 1 (tokenize)
    tok_times = np.concatenate([np.zeros(scen.num_map, np.int32),
                                1 + np.arange(slab - scen.num_map) // a])
    # each slot has just appended step t's agents: the decode queries
    t = rng.integers(1, scen.num_steps + 1, size=b)
    kvl = jnp.asarray(scen.num_map + a * t, jnp.int32)
    q_times = jnp.asarray(np.broadcast_to(t[:, None], (b, a)), jnp.int32)
    k_times = jnp.asarray(np.broadcast_to(tok_times, (b, slab)), jnp.int32)
    k_seg = jnp.asarray(np.where(rng.random((b, slab)) < 0.1, -1, 0),
                        jnp.int32)
    q_seg = jnp.zeros((b, a), jnp.int32)
    q = normal(b, h, a, c)
    k, v = normal(layers, b, h, slab, c), normal(layers, b, h, slab, c)
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    # the model's stacked cache is feature-major: (L, B, H, c, S)
    k, v, kq, vq = (jnp.swapaxes(x, -1, -2) for x in (k, v, kq, vq))
    errs, twin_errs, compile_s, kernels = {}, {}, 0.0, 0

    def compare(names, got, twin, want, keep=1.0):
        """``got``: the kernels; ``twin``: their XLA path at default matmul
        precision; ``want``: the reference at highest precision; ``keep``
        masks the rows compared."""
        for name, x, t, y in zip(names, got, twin, want):
            x, t, y = (np.asarray(a) * keep for a in (x, t, y))
            errs[name] = float(np.max(np.abs(x - y)))
            twin_errs[name] = float(np.max(np.abs(t - y)))
            np.testing.assert_allclose(x, y, err_msg=name, **PARITY_TOL)
            np.testing.assert_allclose(x, t, err_msg=f"{name} vs XLA path",
                                       **TWIN_TOL)

    def decode(impl):
        def fn(q, k, v, k_scale, v_scale):
            return ops.decode_attention(
                q, k, v, kv_length=kvl, impl=impl, layer=1,
                q_times=q_times, k_times=k_times, q_segment_ids=q_seg,
                k_segment_ids=k_seg, k_scale=k_scale, v_scale=v_scale)
        return fn

    for name, args in (("decode_f32", (q, k, v, None, None)),
                       ("decode_int8", (q, kq, vq, ks, vs))):
        compiled, secs, n = _aot(decode("flash_decode"), *args)
        twin = jax.jit(decode("xla"))(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(decode("ref"))(*args)
        compare((name,), (compiled(*args),), (twin,), (want,))
        compile_s, kernels = compile_s + secs, kernels + n

    # flash forward + gradients over a block-causal scene, as in training
    times = jnp.asarray(np.broadcast_to(tok_times[:s], (b, s)), jnp.int32)
    seg = jnp.asarray(np.where(rng.random((b, s)) < 0.1, -1, 0), jnp.int32)
    qf, kf, vf = normal(b, h, s, c), normal(b, h, s, c), normal(b, h, s, c)
    # A padded row (segment -1) reaches no key: the kernels and the
    # reference output zero there, the chunked XLA path the mean of the
    # values other rows reach. Such rows carry no loss, so their cotangent
    # is zero and their outputs are not compared.
    live = np.asarray(seg >= 0)[:, None, :, None]
    g = normal(b, h, s, c) * live
    kw = dict(causal=True, q_times=times, k_times=times, q_segment_ids=seg,
              k_segment_ids=seg)

    def attn_and_grads(impl):
        def fn(q, k, v, g):
            out, vjp = jax.vjp(
                lambda *x: ops.attention(*x, impl=impl, **kw), q, k, v)
            return (out,) + vjp(g)
        return fn

    compiled, secs, n = _aot(attn_and_grads("flash"), qf, kf, vf, g)
    twin = jax.jit(attn_and_grads("chunked"))(qf, kf, vf, g)
    with jax.default_matmul_precision("highest"):
        want = (jax.jit(lambda q, k, v: ref.mha_reference(q, k, v, **kw))(
            qf, kf, vf),) + tuple(jax.jit(
                lambda *x: ref.mha_grads_reference(*x, **kw))(qf, kf, vf, g))
    got = compiled(qf, kf, vf, g)
    compare(("flash_fwd",), got[:1], twin[:1], want[:1], keep=live)
    compare(("flash_dq", "flash_dk", "flash_dv"), got[1:], twin[1:], want[1:])
    compile_s, kernels = compile_s + secs, kernels + n
    _phase_line("kernels", device, compile_s=compile_s, kernel_calls=kernels,
                row_width=c, max_abs_err=errs, xla_path_max_abs_err=twin_errs)


def serve_phase(device, model, params, arch, cache_dtype, seed):
    import numpy as np

    from repro import obs
    from repro.runtime.sim_server import SceneRequest, SimServer, poisson_drive
    from repro.scenarios.registry import generate_mixed

    scen = arch.scenario_config()
    reg = obs.Registry()
    srv = SimServer(model, params, scen, num_slots=SLOTS,
                    cache_dtype=cache_dtype, registry=reg)
    reqs = [SceneRequest(uid=i, tensors=sc, t_hist=T_HIST, seed=seed,
                         scene_id=i)
            for i, sc in enumerate(generate_mixed(seed, 0, SCENES, scen))]
    poisson_drive(srv, reqs, rate=1.0, seed=seed, warmup_ticks=1)
    stats = srv.stats()
    check(len(srv.done) == SCENES, f"{len(srv.done)}/{SCENES} drained")
    bad = [r.uid for r in srv.done.values()
           if r.status != "ok" or not np.isfinite(r.future).all()]
    check(not bad, f"scenes with failed or non-finite trajectories: {bad}")
    check(stats["tick_compilations"] == 1
          and stats["admit_compilations"] == 1, f"recompiled: {stats}")
    kernels = {p: int(_gauge(reg, "cost.kernel_calls", p))
               for p in ("sim_server.tick", "sim_server.admit")}
    check(kernels["sim_server.tick"] > 0, "tick runs no Pallas kernel")
    _phase_line(
        f"serve/{cache_dtype}", device,
        compile_s={p: _gauge(reg, "cost.compile_seconds", p)
                   for p in kernels},
        kernel_calls=kernels, ticks=int(stats["ticks"]),
        scenes_done=len(srv.done),
        tick_compilations=int(stats["tick_compilations"]),
        admit_compilations=int(stats["admit_compilations"]))


def train_phase(device, model, params, arch, seed):
    import jax
    import numpy as np

    from repro import obs
    from repro.data.pipeline import ShardedIterator
    from repro.runtime.trainer import Trainer, TrainerConfig
    from repro.training.data import make_batch_fn
    from repro.training.steps import (bc_optimizer, loss_summary,
                                      make_sim_train_step)

    scen = arch.scenario_config()
    reg = obs.Registry()
    opt = bc_optimizer(3e-3, TRAIN_STEPS)
    step = obs.CostAccounted(jax.jit(make_sim_train_step(model, opt)),
                             "train.step", registry=reg)
    data = ShardedIterator(make_batch_fn(scen), batch_size=TRAIN_BATCH,
                           seed=seed)
    with tempfile.TemporaryDirectory() as ckpt_dir:
        trainer = Trainer(step, params, opt.init(params), data, ckpt_dir,
                          TrainerConfig(total_steps=TRAIN_STEPS,
                                        ckpt_every=TRAIN_STEPS),
                          registry=reg)
        try:
            out = trainer.run()
        finally:
            data.close()
    summary = loss_summary(trainer.history)
    check(out["status"] == "done" and out["nan_skipped"] == 0, out)
    check(np.isfinite(trainer.history).all(), trainer.history)
    check(summary["loss_last"] < summary["loss_first"],
          f"loss did not fall: {summary}")
    kernels = int(_gauge(reg, "cost.kernel_calls", "train.step"))
    check(kernels > 0, "train step runs no Pallas kernel")
    _phase_line("train", device,
                compile_s=_gauge(reg, "cost.compile_seconds", "train.step"),
                kernel_calls=kernels, steps=trainer.step, **summary)


def fleet_phase(devices, model, params, arch, seed):
    """Scene-sharded engine on all four chips == the one-device engine."""
    import numpy as np

    from repro import obs
    from repro.launch.mesh import make_fleet_mesh
    from repro.runtime.rollout import RolloutEngine
    from repro.scenarios.registry import generate_mixed

    scen = arch.scenario_config()
    reg = obs.Registry()
    scenes = generate_mixed(seed, 0, 8, scen)
    one = RolloutEngine(model, params, scen, num_slots=SLOTS, registry=reg)
    fleet = RolloutEngine(model, params, scen, num_slots=SLOTS,
                          mesh=make_fleet_mesh(len(devices)), registry=reg)
    f1 = one.run(scenes, t_hist=T_HIST, n_samples=2, seed=seed)
    f4 = fleet.run(scenes, t_hist=T_HIST, n_samples=2, seed=seed)
    check(np.isfinite(f1).all(), "non-finite one-device futures")
    check(np.array_equal(f1, f4)
          and np.array_equal(one.last_actions, fleet.last_actions),
          "fleet rollouts differ from the one-device engine")
    # the lanes, and so the work, are spread over every chip
    k = fleet.init_cache()["k"]
    lanes = {s.device.id: s.data.shape[1] for s in k.addressable_shards}
    check(sorted(lanes) == sorted(d.id for d in devices)
          and set(lanes.values()) == {SLOTS // len(devices)},
          f"lanes per device: {lanes}")
    _phase_line("fleet", devices[0],
                compile_s=_gauge(reg, "cost.compile_seconds", "rollout.step"),
                kernel_calls=int(_gauge(reg, "cost.kernel_calls",
                                        "rollout.step")),
                bit_identical=True, lanes_per_device=lanes)


def sharded_train_phase(devices, model, params, arch, seed):
    """One BC step on a (data, model) mesh of all chips == one device; then
    the trainer's eval hook on the sharded parameters."""
    import jax
    import numpy as np

    from repro.distributed.sharding import (batch_sharding,
                                            derive_opt_shardings,
                                            sharding_for_specs,
                                            use_mesh_rules)
    from repro.launch.mesh import make_mesh_for
    from repro.launch.train_sim import make_eval_cb
    from repro.training.data import holdout_batches, make_batch_fn
    from repro.training.steps import bc_optimizer, make_sim_train_step

    scen = arch.scenario_config()
    specs = model.specs()
    opt = bc_optimizer(3e-3, TRAIN_STEPS)
    opt_state = opt.init(params)
    batch = make_batch_fn(scen)(seed, 0, TRAIN_BATCH)
    step = make_sim_train_step(model, opt)
    p1, o1, m1 = jax.jit(step)(params, opt_state, batch)

    mesh = make_mesh_for(len(devices))
    with use_mesh_rules(mesh):
        psh = sharding_for_specs(specs, mesh)
        osh = derive_opt_shardings(specs, jax.eval_shape(opt.init, params),
                                   mesh)
        bsh = {k: batch_sharding(mesh, v.shape) for k, v in batch.items()}
        args = (jax.device_put(params, psh), jax.device_put(opt_state, osh),
                jax.device_put(batch, bsh))
        t0 = time.perf_counter()
        sstep = jax.jit(step, in_shardings=(psh, osh, bsh),
                        out_shardings=(psh, osh, None)).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        p4, o4, m4 = sstep(*args)
        leaves = jax.tree.leaves(p4)
        used = {d.id for leaf in leaves for d in leaf.sharding.device_set}
        check(used == {d.id for d in devices}
              and not all(leaf.sharding.is_fully_replicated
                          for leaf in leaves),
              f"parameters not sharded over every chip: {used}")
        eval_cb, state = make_eval_cb(
            model, scen, holdout=holdout_batches(scen, TRAIN_BATCH, 1, seed),
            n_scenes_per_family=1, n_samples=2, seed=seed)
        eval_cb(1, p4)
    loss_diff = abs(float(m1["loss"]) - float(m4["loss"]))
    gnorm_rel = abs(float(m1["grad_norm"]) / float(m4["grad_norm"]) - 1)
    check(loss_diff < 1e-3 and gnorm_rel < 1e-3,
          f"sharded step differs: loss {loss_diff}, grad norm {gnorm_rel}")
    # bc_optimizer is chain(clip, adamw): after Adam's first step its first
    # moment is (1 - b1) = 0.1 times the clipped gradient, so the two
    # gradients are compared leaf by leaf through the optimizer state
    names = [jax.tree_util.keystr(k)
             for k, _ in jax.tree_util.tree_flatten_with_path(params)[0]]
    g1, g4 = ([np.asarray(m) / 0.1 for m in jax.tree.leaves(o[-1]["mu"])]
              for o in (o1, o4))
    grad_rel = {n: float(np.max(np.abs(b - a)) / max(np.max(np.abs(a)),
                                                     1e-30))
                for n, a, b in zip(names, g1, g4)}
    worst = max(grad_rel, key=grad_rel.get)
    check(grad_rel[worst] < GRAD_TOL,
          f"sharded gradient of {worst} differs by {grad_rel[worst]}")
    # Adam's first step moves an element by lr * g / (|g| + eps), about
    # +-lr whatever |g| is: where summation order flips the sign of a
    # near-zero gradient the parameters differ by up to 2 lr. Everywhere
    # the signs agree and |g| >> eps they must agree.
    param_diff = flip_diff = 0.0
    compared = flips = total = 0
    for a, b, ga, gb in zip(jax.tree.leaves(p1), jax.tree.leaves(p4), g1, g4):
        d = np.abs(np.asarray(a) - np.asarray(b))
        flip = np.sign(ga) != np.sign(gb)
        same = ~flip & (np.abs(ga) > 1e-5)
        param_diff = max(param_diff, float(d.max(initial=0, where=same)))
        flip_diff = max(flip_diff, float(d.max(initial=0, where=flip)))
        compared, flips = compared + int(same.sum()), flips + int(flip.sum())
        total += d.size
    check(param_diff < PARAM_TOL and compared > total // 2,
          f"sharded update differs by {param_diff} on {compared}/{total} "
          f"elements whose gradient signs agree")
    closed = state["last"]["closed_loop"]
    check(all(np.isfinite(closed[m]) for m in ("min_ade", "miss_rate")),
          f"eval hook metrics: {closed}")
    kernels = _kernels(sstep)
    _phase_line("sharded_train", devices[0], compile_s=compile_s,
                kernel_calls=kernels, loss_diff=loss_diff,
                grad_norm_rel_diff=gnorm_rel,
                grad_max_rel_diff={worst: grad_rel[worst]},
                param_max_diff=param_diff, params_compared=compared,
                params_total=total, grad_sign_flips=flips,
                param_max_diff_at_flips=flip_diff,
                mesh=dict(mesh.shape),
                eval_min_ade=closed["min_ade"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{devices[0].platform!r}); this check has no CPU fallback",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} chips, "
              f"JAX sees {len(devices)}", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compilation cache: {enable_compile_cache()}", flush=True)

    arch, model, params = _build(args.seed)
    if args.chips == 1:
        kernels_phase(devices[0], model, arch, args.seed)
        for cache_dtype in ("float32", "int8"):
            serve_phase(devices[0], model, params, arch, cache_dtype,
                        args.seed)
        train_phase(devices[0], model, params, arch, args.seed)
    else:
        devices = devices[:args.chips]
        fleet_phase(devices, model, params, arch, args.seed)
        sharded_train_phase(devices, model, params, arch, args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
