"""Multi-device tests (8 host devices via subprocess — device count is locked
at first jax init, so each scenario runs in its own interpreter)."""
import os
import subprocess
import sys
import textwrap

ENV = {**os.environ, "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
       "PYTHONPATH": "src"}


def run_py(code: str) -> str:
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=ENV,
                       cwd=os.path.dirname(os.path.dirname(__file__)),
                       timeout=600)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_tp_sharded_train_step_matches_single_device():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_smoke_config
        from repro.core.engine import ArcaneEngine
        from repro.models.transformer import LM
        from repro.optim.adamw import AdamWConfig, adamw_init
        from repro.train.step import make_train_step
        from repro.distributed.sharding import (param_pspecs, batch_pspecs,
                                                to_shardings, zero_pspecs)
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke_config("qwen2.5-32b")
        cfg = dataclasses.replace(cfg, param_dtype="float32",
                                  compute_dtype="float32")
        model = LM(cfg, ArcaneEngine(backend="ref"))
        params = model.init_params(jax.random.key(0))
        opt_cfg = AdamWConfig(total_steps=10, warmup_steps=0)
        opt = adamw_init(opt_cfg, params)
        rngn = np.random.default_rng(0)
        batch = {"tokens": jnp.asarray(rngn.integers(0, cfg.vocab, (8, 32)))}
        step = make_train_step(model, opt_cfg)
        # single device reference
        p_ref, _, m_ref = jax.jit(step)(params, opt, batch)
        # sharded (2 data x 4 model)
        mesh = make_host_mesh(model_axis=4)
        with mesh:
            p_sh = to_shardings(param_pspecs(params, mesh), mesh)
            o_sh = to_shardings(zero_pspecs(opt, mesh), mesh)
            b_sh = to_shardings(batch_pspecs(batch, mesh), mesh)
            fn = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, None))
            p_out, _, m_out = fn(params, opt, batch)
        assert abs(float(m_ref["loss"]) - float(m_out["loss"])) < 1e-4
        for a, b in zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_out)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-4, rtol=2e-3)
        print("TP_OK")
    """)
    assert "TP_OK" in out


def test_compressed_dp_converges_like_uncompressed():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import get_smoke_config
        from repro.core.engine import ArcaneEngine
        from repro.models.transformer import LM
        from repro.optim.adamw import AdamWConfig, adamw_init
        from repro.distributed.collectives import (make_compressed_dp_step,
                                                   init_error_feedback)
        from repro.launch.mesh import make_host_mesh
        from repro.data.pipeline import DataConfig, SyntheticLM

        cfg = get_smoke_config("stablelm-3b")
        cfg = dataclasses.replace(cfg, param_dtype="float32",
                                  compute_dtype="float32")
        model = LM(cfg, ArcaneEngine(backend="ref"))
        mesh = make_host_mesh(model_axis=1)   # 8-way DP
        opt_cfg = AdamWConfig(lr=3e-3, total_steps=30, warmup_steps=3)
        src = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32,
                                     global_batch=8))

        def train(compress):
            params = model.init_params(jax.random.key(0))
            opt = adamw_init(opt_cfg, params)
            err = init_error_feedback(params)
            step = make_compressed_dp_step(model, opt_cfg, mesh,
                                           compress=compress)
            with mesh:
                losses = []
                for i in range(30):
                    batch = {k: jnp.asarray(v)
                             for k, v in src.batch_at(i).items()}
                    params, opt, err, m = step(params, opt, err, batch)
                    losses.append(float(m["loss"]))
            return losses

        lc = train(True)
        lu = train(False)
        assert lc[-1] < lc[0] - 0.3, lc
        assert abs(lc[-1] - lu[-1]) < 0.25, (lc[-1], lu[-1])
        print("DP_COMPRESS_OK")
    """)
    assert "DP_COMPRESS_OK" in out


def test_pipeline_parallel_forward_parity():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.distributed.pipeline import pipeline_forward
        mesh = jax.make_mesh((4,), ("stage",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        rngn = np.random.default_rng(0)
        ws = jnp.asarray(rngn.standard_normal((4, 16, 16)) * 0.3,
                         jnp.float32)
        x = jnp.asarray(rngn.standard_normal((8, 16)), jnp.float32)

        def stage_fn(w, h):
            return jnp.tanh(h @ w)

        ref = x
        for i in range(4):
            ref = stage_fn(ws[i], ref)
        out = pipeline_forward(stage_fn, ws, x, mesh=mesh, n_micro=4)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5)
        print("PP_OK")
    """)
    assert "PP_OK" in out


def test_elastic_checkpoint_restore_across_meshes(tmp_path):
    out = run_py(f"""
        import jax, jax.numpy as jnp, numpy as np
        from repro.checkpoint.manager import CheckpointManager
        from repro.launch.mesh import make_host_mesh
        from jax.sharding import NamedSharding, PartitionSpec as P

        tree = {{"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}}
        mgr = CheckpointManager({str(tmp_path)!r})
        mesh8 = make_host_mesh(model_axis=8)
        sh8 = {{"w": NamedSharding(mesh8, P(None, "model"))}}
        tree8 = jax.device_put(tree, sh8["w"])
        mgr.save(1, {{"w": tree8}})
        # restore onto a DIFFERENT mesh layout (2-way model)
        mesh2 = make_host_mesh(model_axis=2)
        sh2 = {{"w": NamedSharding(mesh2, P("model", None))}}
        like = jax.eval_shape(lambda: tree)
        restored, _ = mgr.restore(1, {{"w": like}}, shardings={{"w": sh2}})
        np.testing.assert_array_equal(np.asarray(restored["w"]["w"]),
                                      np.asarray(tree["w"]))
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out
