"""shard_map expert-parallel MoE ≡ the pjit dispatch (numerics), verified on
an 8-device subprocess mesh."""

import os
import subprocess
import sys
import textwrap

import pytest


@pytest.mark.slow
def test_ep_shardmap_matches_pjit_dispatch():
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import moe as M
        from repro.models import layers as L
        from repro.launch.mesh import make_mesh, MeshAxes

        cfg = get_config("deepseek-moe-16b").reduced()
        # 8 experts over a 4-wide model axis; huge capacity → no drops, so
        # both dispatch algorithms compute the identical function
        cfg = dataclasses.replace(cfg, moe_capacity_factor=float(cfg.n_experts))
        key = jax.random.key(0)
        p = M.init_moe_ffn(key, cfg)
        x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model),
                              jnp.float32)

        ref, _ = M.moe_ffn(p, x, cfg)

        mesh = make_mesh((2, 4), ("data", "model"))
        ax = MeshAxes(mesh)
        L.set_shard_ctx(mesh, ax.dp, ax.model)
        with mesh:
            got, _ = jax.jit(lambda p, x: M.moe_ffn_shardmap(p, x, cfg))(p, x)
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-2, atol=2e-2)
        print("EP_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, cwd="/root/repo",
                         timeout=600)
    assert "EP_OK" in out.stdout, out.stderr[-3000:]


@pytest.mark.slow
def test_ep_shardmap_grad_matches_pjit_dispatch():
    """Training differentiates through the shard_map expert layer: its
    gradients, with respect to every expert weight, the router and the
    input, match the pjit dispatch's on a dropless capacity."""
    script = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_config
        from repro.models import moe as M
        from repro.models import layers as L
        from repro.launch.mesh import make_mesh, MeshAxes

        cfg = get_config("deepseek-moe-16b").reduced()
        cfg = dataclasses.replace(cfg, moe_capacity_factor=float(cfg.n_experts))
        p = M.init_moe_ffn(jax.random.key(0), cfg)
        p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
        x = jax.random.normal(jax.random.key(1), (4, 16, cfg.d_model),
                              jnp.float32)

        def loss(ffn):
            def f(p, x):
                out, _ = ffn(p, x)
                return jnp.sum(out * out)
            return jax.grad(f, argnums=(0, 1))

        with jax.default_matmul_precision("highest"):
            want = loss(lambda p, x: M.moe_ffn(p, x, cfg))(p, x)
            mesh = make_mesh((2, 4), ("data", "model"))
            ax = MeshAxes(mesh)
            L.set_shard_ctx(mesh, ax.dp, ax.model)
            with mesh:
                got = jax.jit(loss(
                    lambda p, x: M.moe_ffn_shardmap(p, x, cfg)))(p, x)
        # f32 sums in another order: a few ulps of each leaf's largest
        # entries (which reach 1e2-1e3 here)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            b = np.asarray(b)
            np.testing.assert_allclose(np.asarray(a), b, rtol=1e-4,
                                       atol=1e-6 * np.abs(b).max())
        print("EP_GRAD_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run([sys.executable, "-c", script], env=env,
                         capture_output=True, text=True, cwd=root,
                         timeout=600)
    assert "EP_GRAD_OK" in out.stdout, out.stderr[-3000:]
