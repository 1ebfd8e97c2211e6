"""Multi-device tests — each runs in a subprocess with its own fake-device
count (jax pins the device count at first init, so the main pytest process
stays single-device)."""

import os
import subprocess
import sys
import textwrap

import pytest

# every test here boots a fresh jax in a subprocess (~30s+ each); keep them
# out of the CI fast lane (-m "not slow")
pytestmark = pytest.mark.slow

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_py(body: str, devices: int = 8, timeout: int = 600) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """One train step on a 2x2 mesh == the same step on 1 device."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models import Model, get_config
        from repro.models.sharding import use_rules, param_shardings
        from repro.launch.steps import init_train_state, make_train_step
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_config('qwen3_4b', smoke=True).replace(dtype='float32')
        m = Model(cfg)
        params, opt = init_train_state(m, jax.random.PRNGKey(0))
        k = jax.random.PRNGKey(1)
        batch = {'tokens': jax.random.randint(k, (4, 32), 0, cfg.vocab_size),
                 'labels': jax.random.randint(k, (4, 32), 0, cfg.vocab_size)}
        step = make_train_step(m)
        p_ref, _, met_ref = jax.jit(step)(params, opt, batch)

        mesh = make_mesh((2, 2), ('data', 'model'))
        with use_rules(mesh):
            p_sh = param_shardings(params)
            params_s = jax.device_put(params, p_sh)
            batch_s = {k2: jax.device_put(v, NamedSharding(mesh, P('data',))) for k2, v in batch.items()}
            p_out, _, met = jax.jit(step)(params_s, opt, batch_s)
        d = max(float(jnp.abs(a - b).max()) for a, b in
                zip(jax.tree.leaves(p_ref), jax.tree.leaves(p_out)))
        assert d < 2e-4, d
        assert abs(float(met['loss']) - float(met_ref['loss'])) < 1e-3
        print('OK', d)
    """)
    assert "OK" in out


def test_context_parallel_attention_matches_local():
    """shard_map seq-sharded attention == single-device attention."""
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.mesh import make_mesh
        from repro.models import layers as L
        from repro.models.config import ModelConfig
        from repro.models.sharding import use_rules

        cfg = ModelConfig(d_model=64, num_heads=4, num_kv_heads=2, head_dim=16,
                          attn_chunk=16, dtype='float32')
        p = L.init_attention(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 64), jnp.float32)
        ref = L.attention_full(cfg, p, x, window=0)
        mesh = make_mesh((2, 4), ('data', 'model'))
        with use_rules(mesh):
            out = jax.jit(lambda x: L.attention_full(cfg, p, x, window=0))(x)
        d = float(jnp.abs(ref - out).max())
        assert d < 1e-3, d
        # windowed variant too
        refw = L.attention_full(cfg, p, x, window=8)
        with use_rules(mesh):
            outw = jax.jit(lambda x: L.attention_full(cfg, p, x, window=8))(x)
        dw = float(jnp.abs(refw - outw).max())
        assert dw < 1e-3, dw
        print('OK', d, dw)
    """)
    assert "OK" in out


def test_moe_block_local_dispatch_sharded_matches():
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.models import layers as L
        from repro.models.config import ModelConfig
        from repro.models.sharding import use_rules

        cfg = ModelConfig(family='moe', d_model=32, num_experts=8, top_k=2,
                          expert_d_ff=64, capacity_factor=2.0, dtype='float32')
        p = L.init_moe(cfg, jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, 32), jnp.float32)
        ref, aux_ref = L.moe(cfg, p, x)   # NB=1 path
        mesh = make_mesh((4, 2), ('data', 'model'))
        with use_rules(mesh):
            out, aux = jax.jit(lambda x: L.moe(cfg, p, x))(x)
        # block-local capacity differs from global capacity only via drops;
        # capacity_factor=2 + small T means no drops -> exact match
        d = float(jnp.abs(ref - out).max())
        assert d < 2e-3, d
        print('OK', d)
    """)
    assert "OK" in out


def test_compressed_psum_multidevice():
    out = run_py("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.launch.mesh import make_mesh
        from repro.runtime import compressed_psum

        mesh = make_mesh((4,), ('x',))
        gs = jax.random.normal(jax.random.PRNGKey(0), (4, 256), jnp.float32)

        def f(g):
            out, _ = compressed_psum(g[0], 'x')
            return out[None]

        out = jax.jit(jax.shard_map(f, mesh=mesh, in_specs=(P('x'),), out_specs=P('x')))(gs)
        want = jnp.mean(gs, axis=0)
        err = float(jnp.abs(out[0] - want).max()) / (float(jnp.abs(want).max()) + 1e-9)
        assert err < 0.05, err
        print('OK', err)
    """)
    assert "OK" in out


def test_elastic_reshard_2x2_to_4x1():
    """Checkpoint on one mesh, restore on another; train continues."""
    out = run_py("""
        import tempfile, jax, jax.numpy as jnp
        from repro.launch.mesh import make_mesh
        from repro.models import Model, get_config
        from repro.models.sharding import use_rules, param_shardings
        from repro.launch.steps import init_train_state, make_train_step
        from repro.checkpoint import save_checkpoint, load_checkpoint
        from jax.sharding import NamedSharding, PartitionSpec as P

        cfg = get_config('minicpm_2b', smoke=True)
        m = Model(cfg)
        params, opt = init_train_state(m, jax.random.PRNGKey(0))
        mesh1 = make_mesh((2, 2), ('data', 'model'))
        with use_rules(mesh1):
            p1 = jax.device_put(params, param_shardings(params))
        with tempfile.TemporaryDirectory() as d:
            save_checkpoint(d, 1, p1)
            mesh2 = make_mesh((4, 1), ('data', 'model'))
            with use_rules(mesh2):
                sh2 = param_shardings(params)
                p2, _ = load_checkpoint(d, 1, params, shardings=sh2)
                k = jax.random.PRNGKey(1)
                batch = {'tokens': jax.random.randint(k, (4, 16), 0, cfg.vocab_size),
                         'labels': jax.random.randint(k, (4, 16), 0, cfg.vocab_size)}
                step = make_train_step(m)
                p3, o3, met = jax.jit(step)(p2, opt, batch)
        assert jnp.isfinite(met['loss'])
        print('OK', float(met['loss']))
    """)
    assert "OK" in out


def test_dryrun_single_cell_small_mesh():
    """The dry-run path end-to-end on an 8-device 4x2 production-mesh stand-in."""
    out = run_py("""
        import jax
        from repro.launch.mesh import make_mesh
        import repro.launch.mesh as mesh_mod
        mesh_mod.make_production_mesh = lambda multi_pod=False: (
            make_mesh((2, 2, 2), ('pod', 'data', 'model')) if multi_pod
            else make_mesh((4, 2), ('data', 'model')))
        import repro.launch.dryrun as dr
        dr.make_production_mesh = mesh_mod.make_production_mesh
        import repro.launch.specs as specs
        from repro.models.registry import get_config
        orig = specs.model_for_cell
        def small(arch, shape, **kw):
            kw.setdefault('overrides', None)
            model, cell = orig(arch, shape, **kw)
            from repro.models.transformer import Model
            import dataclasses
            cfg = get_config(arch, smoke=True)
            cell2 = dataclasses.replace(cell, seq_len=64, global_batch=8)
            return Model(cfg, remat='full'), cell2
        dr.model_for_cell = small
        for shape in ('train_4k', 'decode_32k'):
            for mp in (False, True):
                rec = dr.lower_cell('qwen3_4b', shape, multi_pod=mp)
                assert rec['hlo_flops'] > 0
        print('OK')
    """, devices=8)
    assert "OK" in out
