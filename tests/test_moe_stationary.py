"""Token-stationary decode MoE ⇔ reference MoE on a real device mesh.

Runs in a subprocess because the 4-virtual-device XLA flag must be set
before JAX initializes (the main test process stays single-device).
"""
import os
import subprocess
import sys
import textwrap

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from repro.configs import get_smoke_config
    from repro.models import moe as moe_mod

    cfg = get_smoke_config("granite_moe_1b")
    cfg = dataclasses.replace(cfg, d_model=128)
    key = jax.random.PRNGKey(0)
    p, _ = moe_mod.moe_init(key, cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, 1, cfg.d_model),
                          jnp.float32)
    y_ref, aux_ref = moe_mod._moe_global(p, cfg, x)
    # hand the ambient abstract mesh to the stationary path (it only reads
    # mesh.shape / mesh.axis_names)
    mesh = jax.make_mesh((2, 2), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    with jax.set_mesh(mesh):
        sm_mesh = jax.sharding.get_abstract_mesh()
        y_st, aux_st = jax.jit(
            lambda pp, xx: moe_mod._moe_decode_stationary(
                pp, cfg, xx, sm_mesh))(p, x)
    assert np.allclose(np.asarray(y_st), np.asarray(y_ref), atol=2e-4), \\
        float(np.abs(np.asarray(y_st) - np.asarray(y_ref)).max())
    assert abs(float(aux_st) - float(aux_ref)) < 1e-5
    print("OK")
""")


def test_token_stationary_equals_reference_on_mesh():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                         capture_output=True, text=True, timeout=600,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-2000:]
    assert "OK" in out.stdout
