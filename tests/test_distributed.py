"""Multi-device distribution tests.

These run in SUBPROCESSES with ``XLA_FLAGS=--xla_force_host_platform_device_
count=8`` so the main test process (and every other test) keeps seeing one
CPU device, per the dry-run isolation rule.
"""
import json
import os
import subprocess
import sys
import textwrap


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_with_devices(code: str, n: int = 8) -> dict:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    prelude = "import json, jax, jax.numpy as jnp\n"
    out = subprocess.run([sys.executable, "-c", prelude + textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=420)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    last = out.stdout.strip().splitlines()[-1]
    return json.loads(last)


def test_sharded_train_step_matches_single_device():
    """pjit train step on a 4x2 mesh == single-device step, bit-for-bit-ish."""
    res = run_with_devices("""
        import numpy as np
        from repro.configs.base import ModelConfig
        from repro.nn import module as nnm
        from repro.nn.transformer import TransformerLM
        from repro.optim import adamw, chain, clip_by_global_norm
        from repro.runtime.steps import make_train_step
        from repro.distributed.sharding import (sharding_for_specs,
            derive_opt_shardings, use_mesh_rules, batch_sharding)
        from repro.launch.mesh import make_mesh_for

        cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                          num_q_heads=4, num_kv_heads=2, d_ff=128,
                          vocab_size=128, head_dim=16, dtype="float32")
        model = TransformerLM(cfg)
        specs = model.specs()
        params = nnm.init_params(specs, jax.random.key(0))
        opt = chain(clip_by_global_norm(1.0), adamw(1e-2))
        opt_state = opt.init(params)
        step = make_train_step(cfg, opt, remat=False)
        rng = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rng.integers(0, 128, (8, 32)), jnp.int32),
                 "labels": jnp.asarray(rng.integers(0, 128, (8, 32)), jnp.int32)}

        # single device reference
        p1, o1, m1 = jax.jit(step)(params, opt_state, batch)

        mesh = make_mesh_for(8, model_axis=2)
        with use_mesh_rules(mesh):
            psh = sharding_for_specs(specs, mesh)
            osh = derive_opt_shardings(specs, jax.eval_shape(opt.init, params),
                                       mesh)
            bsh = {k: batch_sharding(mesh, v.shape) for k, v in batch.items()}
            sp = jax.device_put(params, psh)
            so = jax.device_put(opt_state, osh)
            sb = {k: jax.device_put(v, bsh[k]) for k, v in batch.items()}
            jstep = jax.jit(step, in_shardings=(psh, osh, bsh),
                            out_shardings=(psh, osh, None))
            p2, o2, m2 = jstep(sp, so, sb)

        dmax = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) -
                                         jnp.asarray(b).astype(jnp.float32))))
                   for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
        print(json.dumps({"loss1": float(m1["loss"]), "loss2": float(m2["loss"]),
                          "param_maxdiff": dmax}))
    """)
    assert abs(res["loss1"] - res["loss2"]) < 1e-4
    assert res["param_maxdiff"] < 1e-3


def test_pipeline_parallel_matches_sequential():
    """4-stage GPipe schedule == running all layers sequentially."""
    res = run_with_devices("""
        import numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.pipeline import (PipelineConfig,
                                                make_pipelined_fn)

        P_STAGES, LAYERS, M, MB, D = 4, 8, 4, 4, 32
        rng = np.random.default_rng(0)
        params = {"w": jnp.asarray(rng.normal(size=(LAYERS, D, D)) * 0.2,
                                   jnp.float32),
                  "b": jnp.asarray(rng.normal(size=(LAYERS, D)) * 0.1,
                                   jnp.float32)}
        x = jnp.asarray(rng.normal(size=(M * MB, D)), jnp.float32)

        def layer(w, b, h):
            return jnp.tanh(h @ w + b)

        def seq_apply(params, x):
            def body(h, wb):
                return layer(wb[0], wb[1], h), None
            h, _ = jax.lax.scan(body, x, (params["w"], params["b"]))
            return h

        def stage_fn(stage_params, h):
            def body(h, wb):
                return layer(wb[0], wb[1], h), None
            h, _ = jax.lax.scan(body, h, (stage_params["w"],
                                          stage_params["b"]))
            return h

        mesh = jax.make_mesh((4, 2), ("pipe", "model"))
        cfg = PipelineConfig(num_stages=P_STAGES, num_microbatches=M)
        piped = make_pipelined_fn(stage_fn, mesh, cfg)
        want = seq_apply(params, x)
        got = piped(params, x)
        err = float(jnp.max(jnp.abs(want - got)))
        print(json.dumps({"err": err,
                          "bubble": cfg.bubble_fraction}))
    """)
    assert res["err"] < 1e-5
    assert abs(res["bubble"] - 3 / 7) < 1e-9


def test_compressed_dp_step_tracks_uncompressed():
    """int8+EF cross-pod reduction converges like the f32 baseline."""
    res = run_with_devices("""
        import numpy as np
        from repro.distributed.dp_compress import make_compressed_dp_step
        from repro.optim import sgd

        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        rng = np.random.default_rng(0)
        w_true = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
        X = jnp.asarray(rng.normal(size=(64, 16)), jnp.float32)
        y = X @ w_true

        def loss_fn(params, batch):
            xb, yb = batch
            pred = xb @ params["w"]
            return jnp.mean((pred - yb) ** 2)

        opt = sgd(0.05)

        def train(compress):
            step = make_compressed_dp_step(loss_fn, opt, mesh,
                                           compress=compress)
            params = {"w": jnp.zeros(16)}
            state = opt.init(params)
            residual = {"w": jnp.zeros(16)}
            losses = []
            for i in range(60):
                params, state, residual, loss = step(params, state, residual,
                                                     (X, y))
                losses.append(float(loss))
            return losses

        lc = train(True)
        lu = train(False)
        print(json.dumps({"final_compressed": lc[-1],
                          "final_uncompressed": lu[-1]}))
    """)
    assert res["final_compressed"] < 1e-2
    assert res["final_uncompressed"] < 1e-2
    assert res["final_compressed"] < res["final_uncompressed"] * 10 + 1e-3


def test_fleet_rollout_sharded_matches_single_device():
    """Scene-sharded fleet eval == single-device engine, BIT-identical.

    The fleet contract (docs/distributed.md): device placement must never
    leak into results — per-slot PRNG keys and validity masks are computed
    on the host from slot identity alone, so the shard_mapped tick is pure
    partitioning. Checked on a ("pod", "data") = (2, 2) mesh, with a slot
    count that doesn't divide the fleet (rounds up with dead lanes) and a
    scene count that forces multiple chunks.
    """
    res = run_with_devices("""
        import numpy as np
        from repro.configs import get_sim_arch
        from repro.launch.mesh import make_fleet_mesh
        from repro.nn import module as nnm
        from repro.nn.agent_sim import AgentSimModel
        from repro.runtime.evaluation import EvalConfig, evaluate_scenes
        from repro.runtime.rollout import RolloutEngine
        from repro.scenarios import registry

        arch = get_sim_arch("sim-se2-fourier").reduced().reduced(
            num_map=12, num_agents=4, num_steps=8)
        scen = arch.scenario_config()
        model = AgentSimModel(arch.agent_sim_config())
        params = nnm.init_params(model.specs(), jax.random.key(0))
        fams = registry.names()
        scenes = [registry.generate_scene(fams[i % len(fams)], 5, i, scen)
                  for i in range(10)]
        cfg = EvalConfig(t_hist=4, n_samples=2, seed=3)

        ref = RolloutEngine(model, params, scen, num_slots=8)
        mesh = make_fleet_mesh(4, pods=2)
        # num_slots=6 does not divide the 4-way fleet: rounds up to 8
        fleet = RolloutEngine(model, params, scen, num_slots=6, mesh=mesh)

        f1 = ref.run([s.tensors for s in scenes], t_hist=4, n_samples=2,
                     seed=3)
        f2 = fleet.run([s.tensors for s in scenes], t_hist=4, n_samples=2,
                       seed=3)
        t1 = evaluate_scenes(ref, scenes, cfg)
        t2 = evaluate_scenes(fleet, scenes, cfg)
        flat = lambda t: {f"{f}/{m}": v for f, row in sorted(t.items())
                          for m, v in sorted(row.items())}
        print(json.dumps({
            "bit_identical": bool(np.array_equal(f1, f2)),
            "rounded_slots": fleet.num_slots,
            "tables_equal": flat(t1) == flat(t2),
            "overall_min_ade": t2["overall"]["min_ade"],
        }))
    """, n=4)
    assert res["bit_identical"], res
    assert res["tables_equal"], res
    assert res["rounded_slots"] == 8
    assert res["overall_min_ade"] == res["overall_min_ade"]  # finite


def test_fleet_mesh_rejects_non_fleet_axes():
    """RolloutEngine only shards scene lanes: a mesh carrying a model axis
    must be rejected loudly, not silently replicate the cache."""
    res = run_with_devices("""
        from repro.configs import get_sim_arch
        from repro.nn import module as nnm
        from repro.nn.agent_sim import AgentSimModel
        from repro.runtime.rollout import RolloutEngine

        arch = get_sim_arch("sim-se2-fourier").reduced().reduced(
            num_map=12, num_agents=4, num_steps=8)
        model = AgentSimModel(arch.agent_sim_config())
        params = nnm.init_params(model.specs(), jax.random.key(0))
        try:
            RolloutEngine(model, params, arch.scenario_config(),
                          num_slots=8,
                          mesh=jax.make_mesh((2, 2), ("data", "model")))
            err = ""
        except ValueError as e:
            err = str(e)
        print(json.dumps({"err": err}))
    """, n=4)
    assert "model" in res["err"], res


def test_elastic_restore_across_mesh_shapes(tmp_path):
    """Checkpoint saved on a 4x2 mesh restores onto 2x4 and 8x1 meshes."""
    res = run_with_devices(f"""
        import numpy as np
        from repro.checkpoint import CheckpointManager
        from repro.configs.base import ModelConfig
        from repro.nn import module as nnm
        from repro.nn.transformer import TransformerLM
        from repro.distributed.sharding import (sharding_for_specs,
                                                use_mesh_rules)
        from repro.launch.mesh import make_mesh_for

        cfg = ModelConfig(name="t", family="dense", num_layers=2, d_model=64,
                          num_q_heads=4, num_kv_heads=2, d_ff=128,
                          vocab_size=128, head_dim=16, dtype="float32")
        model = TransformerLM(cfg)
        specs = model.specs()
        mgr = CheckpointManager({json.dumps(str(tmp_path))}, async_save=False)

        mesh_a = make_mesh_for(8, model_axis=2)
        psh_a = sharding_for_specs(specs, mesh_a)
        params = jax.device_put(nnm.init_params(specs, jax.random.key(0)),
                                psh_a)
        mgr.save(1, {{"params": params}}, extra={{"step": 1}})

        diffs = []
        for shape in ((2, 4), (8, 1)):
            mesh_b = make_mesh_for(8, model_axis=shape[1])
            psh_b = sharding_for_specs(specs, mesh_b)
            tree, _ = mgr.restore(1, shardings={{"params": psh_b}})
            diffs.append(max(float(jnp.max(jnp.abs(
                jnp.asarray(a) - jnp.asarray(b))))
                for a, b in zip(jax.tree.leaves(params),
                                jax.tree.leaves(tree["params"]))))
        print(json.dumps({{"maxdiff": max(diffs)}}))
    """)
    assert res["maxdiff"] == 0.0
