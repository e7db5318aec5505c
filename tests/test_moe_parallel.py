"""shard_map MoE + capacity dispatch: exactness vs the dense path.

Runs in a subprocess with 8 forced host devices (the main pytest process
is pinned to 1 device — device count locks at first jax init)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.models.config import ModelConfig
    from repro.models import moe

    cfg = ModelConfig(name="m", family="moe", num_layers=1, d_model=32,
                      num_heads=2, num_kv_heads=1, d_ff=64, vocab_size=64,
                      num_experts=4, experts_per_token=2)
    p = moe.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    # enough tokens per shard to take the real shard_map path (>= 64)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 32, 32))
    ref = moe.apply_moe(p, cfg, x)
    mesh = Mesh(np.asarray(jax.devices()).reshape(2, 4), ("data", "model"))
    moe.set_parallel_mesh(mesh)
    for dispatch in ("ragged", "capacity"):
        moe.set_dispatch(dispatch)
        with mesh:
            out, aux = moe._apply_moe_shard_map(p, cfg, x)
        tol = 2e-5 if dispatch == "ragged" else 5e-3
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=tol, rtol=tol)
        assert float(aux.get("drop_fraction", 0.0)) <= 0.05
    moe.set_parallel_mesh(None); moe.set_dispatch("ragged")
    # capacity drop accounting on a deliberately tight cap
    out, aux = moe._moe_capacity_math(p, cfg, x.reshape(-1, 32),
                                      capacity_factor=0.5)
    assert 0.0 < float(aux["drop_fraction"]) < 1.0
    print("MOE_PARALLEL_OK")
""")


@pytest.mark.slow
def test_shard_map_moe_exact_in_subprocess():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"     # never reach for a chip the parent holds
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=420,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert "MOE_PARALLEL_OK" in r.stdout, r.stderr[-3000:]
