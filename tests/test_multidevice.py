"""Multi-device behaviour on forced host devices (subprocess: the device
count must be fixed before jax initializes, and the main test process
must keep seeing 1 device). The forced-mesh env/subprocess machinery is
shared with the conformance matrix (``repro.conformance.subproc``)."""
import pytest

from repro.conformance import run_py


def test_dp_tp_train_step_matches_single_device():
    out = run_py("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config, reduced
        from repro.models import init_params
        from repro.train.step import build_train_step
        from repro.train.optimizer import AdamWConfig, init_state
        from repro.launch.mesh import make_mesh
        cfg = reduced(get_config('granite-8b'))
        key = jax.random.PRNGKey(0)
        ocfg = AdamWConfig(warmup_steps=0, total_steps=10)
        batch = {'tokens': jax.random.randint(key,(4,32),0,cfg.vocab_size),
                 'targets': jax.random.randint(key,(4,32),0,cfg.vocab_size)}
        losses = []
        for shape, axes in [((1,1),('data','model')), ((2,2),('data','model')),
                            ((4,1),('data','model')), ((1,4),('data','model'))]:
            mesh = make_mesh(shape, axes)
            params = init_params(cfg, key)
            opt = init_state(ocfg, params)
            built = build_train_step(cfg, mesh, ocfg, donate=False)
            _, _, m = built.fn(params, opt, batch)
            losses.append(float(m['loss']))
        print('LOSSES', losses)
        assert max(losses) - min(losses) < 1e-3, losses
        print('OK')
    """)
    assert "OK" in out


def test_pipeline_runtime_exact_fwd_and_grad():
    out = run_py("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from repro.pipeline.pardnn_pp import (plan_stages, stack_stage_params,
                                              pipeline_apply)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ('stage',))
        key = jax.random.PRNGKey(0)
        L, D, M, mb = 8, 8, 4, 2
        W = jax.random.normal(key, (L, D, D)) * 0.3
        plan = plan_stages(np.ones(L), np.ones(L), 0.0, 4)
        x = jax.random.normal(key, (M, mb, D))
        layer_fn = lambda w, h: jnp.tanh(h @ w)
        def loss(Wf):
            sp, mask = stack_stage_params(Wf, plan.boundaries)
            return jnp.sum(pipeline_apply(mesh, layer_fn, sp, mask, x) ** 2)
        def loss_ref(Wf):
            h = x.reshape(M * mb, D)
            for i in range(L):
                h = jnp.tanh(h @ Wf[i])
            return jnp.sum(h ** 2)
        np.testing.assert_allclose(loss(W), loss_ref(W), rtol=1e-5)
        g, gr = jax.grad(loss)(W), jax.grad(loss_ref)(W)
        np.testing.assert_allclose(g, gr, atol=1e-5)
        print('OK')
    """)
    assert "OK" in out


def test_compressed_psum_over_pod_axis():
    out = run_py("""
        import warnings; warnings.filterwarnings('ignore')
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.train.compression import (make_compressed_psum,
                                             init_error_state)
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ('pod',))
        key = jax.random.PRNGKey(0)
        grads = {'w': jax.random.normal(key, (4, 32, 8))}
        errors = init_error_state({'w': jnp.zeros((32, 8))})
        out, new_e = make_compressed_psum(mesh)(grads, errors)
        ref = jnp.mean(grads['w'], 0)
        rel = float(jnp.max(jnp.abs(out['w'][0] - ref))
                    / jnp.max(jnp.abs(ref)))
        assert rel < 0.03, rel
        # error feedback: residual + dequantized == original (per shard)
        print('OK', rel)
    """)
    assert "OK" in out


def test_elastic_checkpoint_restore_across_meshes():
    """Save sharded on a 4-device mesh, restore onto 2 devices (elastic)."""
    out = run_py("""
        import warnings; warnings.filterwarnings('ignore')
        import tempfile
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager
        from repro.launch.mesh import make_mesh
        mesh4 = make_mesh((4,), ('model',))
        x = jax.device_put(jnp.arange(16.0).reshape(4, 4),
                           NamedSharding(mesh4, P('model', None)))
        with tempfile.TemporaryDirectory() as td:
            ck = CheckpointManager(td)
            ck.save(1, {'x': x})
            mesh2 = make_mesh((2,), ('model',), devices=jax.devices()[:2])
            sh2 = {'x': NamedSharding(mesh2, P('model', None))}
            restored, _ = ck.restore({'x': x}, shardings=sh2)
            np.testing.assert_array_equal(np.asarray(restored['x']),
                                          np.arange(16.0).reshape(4, 4))
            assert len(restored['x'].sharding.device_set) == 2
        print('OK')
    """)
    assert "OK" in out
