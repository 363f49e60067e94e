"""Distributed tests on a forced 8-host-device mesh (subprocess — the main
test process must keep the real 1-device CPU view)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=900,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_pjit_train_step_matches_single_device():
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro import configs
    from repro.dist import sharding
    from repro.models import model
    from repro.optim import optimizers
    from repro.train import step as step_lib

    cfg = configs.get_smoke('minicpm-2b')
    key = jax.random.PRNGKey(0)
    params = model.init_params(key, cfg)
    opt = optimizers.adamw(1e-3, max_grad_norm=1.0)
    toks = jax.random.randint(key, (8, 16), 0, cfg.vocab)
    batch = {'tokens': toks, 'labels': jnp.roll(toks, -1, 1)}

    # single device
    step1 = step_lib.make_train_step(cfg, opt)
    p1, o1, m1 = jax.jit(step1)(params, opt.init(params), batch)

    # 2x4 mesh
    mesh = jax.make_mesh((2, 4), ('data', 'model'),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rules = sharding.BASE_RULES
    ps = step_lib.param_shardings(mesh, cfg, rules)
    with sharding.sharding_ctx(mesh, rules):
        p_sh = jax.device_put(params, ps)
        o_sh = jax.jit(opt.init, out_shardings=step_lib.opt_shardings(mesh, cfg, rules))(p_sh)
        p2, o2, m2 = jax.jit(step_lib.make_train_step(cfg, opt))(p_sh, o_sh, batch)

    np.testing.assert_allclose(float(m1['loss']), float(m2['loss']), rtol=1e-4)
    l1 = jax.tree.leaves(p1); l2 = jax.tree.leaves(p2)
    for a, b in zip(l1, l2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-3, atol=2e-3)
    print('OK')
    """)


def test_elastic_checkpoint_reshard():
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np, tempfile
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.train import checkpoint as ckpt_lib

    tree = {'w': jnp.arange(64.0).reshape(8, 8), 's': jnp.float32(3.0)}
    d = tempfile.mkdtemp()
    mesh_a = jax.make_mesh((2, 4), ('data', 'model'))
    sh_a = {'w': NamedSharding(mesh_a, P('data', 'model')), 's': NamedSharding(mesh_a, P())}
    tree_a = jax.device_put(tree, sh_a)
    ck = ckpt_lib.Checkpointer(d, async_save=False)
    ck.save(1, tree_a)

    # restore onto a DIFFERENT mesh shape
    mesh_b = jax.make_mesh((4, 2), ('data', 'model'))
    sh_b = {'w': NamedSharding(mesh_b, P('model', 'data')), 's': NamedSharding(mesh_b, P())}
    restored, manifest = ck.restore(1, tree, sh_b)
    np.testing.assert_allclose(np.asarray(restored['w']), np.arange(64.0).reshape(8, 8))
    assert restored['w'].sharding == sh_b['w']
    print('OK')
    """)


def test_pipeline_parallel_forward_equivalence():
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from repro.dist import pipeline_parallel as pp

    mesh = jax.make_mesh((8,), ('pod',))
    L, D = 8, 16
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (L, D, D)) * (1.0 / np.sqrt(D))

    def fwd_block(params, x):
        # params: [L/S, D, D] — apply each layer in the stage
        def body(x, wi):
            return jax.nn.tanh(x @ wi), None
        x, _ = jax.lax.scan(body, x, params)
        return x

    M, mb = 4, 8
    x = jax.random.normal(jax.random.PRNGKey(1), (M, mb, D))

    # reference: sequential
    ref = fwd_block(w, x.reshape(M * mb, D)).reshape(M, mb, D)

    stage_params = pp.split_stages(w, 8)
    out = pp.pipeline_forward(fwd_block, stage_params, x, mesh, axis='pod')
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-4, atol=2e-4)
    print('OK')
    """)


def test_codec_train_step_data_parallel():
    """The paper's own compression step runs data-parallel over entries."""
    run_sub("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import codec, nttd
    from repro.core.folding import make_folding_spec
    from repro.optim import optimizers

    spec = make_folding_spec((16, 12, 10))
    cfg = nttd.NTTDConfig(rank=4, hidden=8)
    params = nttd.init_params(jax.random.PRNGKey(0), spec, cfg)
    opt = optimizers.adam(1e-2)
    ost = opt.init(params)
    step = codec._make_train_epoch(spec, cfg, opt)

    rng = np.random.default_rng(0)
    pos = np.stack([rng.integers(0, n, (4, 512)) for n in spec.shape], -1)
    vals = rng.normal(size=(4, 512)).astype(np.float32)

    p1, o1, l1 = step(params, ost, jnp.asarray(pos, jnp.int32), jnp.asarray(vals))

    mesh = jax.make_mesh((8,), ('data',))
    shp = NamedSharding(mesh, P(None, 'data'))
    p2, o2, l2 = jax.jit(step, in_shardings=(None, None, shp, shp))(
        params, ost, jnp.asarray(pos, jnp.int32), jnp.asarray(vals))
    np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4, atol=1e-5)
    print('OK')
    """)


@pytest.mark.slow
def test_dryrun_smoke_cell():
    """One reduced dry-run cell end-to-end in a subprocess (512 devices)."""
    run_sub("""
    import os
    os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=512'
    from repro.launch import dryrun
    res = dryrun.run_cell('mamba2-1.3b', 'decode_32k', 'single', verbose=False)
    assert res['status'] == 'ok', res
    assert res['roofline']['bound_s'] > 0
    assert res['flops_per_device'] > 0
    print('OK')
    """, devices=512)
