"""Grouped MoE dispatch under a real (8 fake device) mesh: the sharded
forward must match the single-device forward (the grouping changes capacity
semantics vs a global dispatch, but must be invariant to the mesh itself)."""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import jax
    from repro.sharding.compat import make_mesh
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_smoke_config
    from repro.models.layers import Ctx
    from repro.models.moe import moe_forward, moe_specs
    from repro.models.params import init_params
    from repro.sharding.rules import make_rules

    cfg = dataclasses.replace(get_smoke_config("moonshot-v1-16b-a3b"),
                              compute_dtype="float32")
    p = init_params(moe_specs(cfg), jax.random.PRNGKey(0), dtype_override=jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, 16, cfg.d_model), jnp.float32)

    mesh = make_mesh((4, 2), ("data", "model"))
    rules = make_rules(mesh, "train")
    ctx_sharded = Ctx(cfg=cfg, rules=rules)
    with jax.sharding.use_mesh(mesh) if hasattr(jax.sharding, "use_mesh") else mesh:
        y_sharded, aux_s, _ = jax.jit(lambda p_, x_: moe_forward(ctx_sharded, p_, x_))(p, x)

    # reference: single-group (G=1) dispatch, no mesh
    ctx_plain = Ctx(cfg=cfg)
    y_plain, aux_p, _ = jax.jit(lambda p_, x_: moe_forward(ctx_plain, p_, x_))(p, x)

    # G=4 grouping changes which tokens drop ONLY when capacity binds; the
    # smoke config uses capacity_factor=8 (no drops), so outputs must agree.
    err = float(jnp.max(jnp.abs(y_sharded - y_plain)))
    assert err < 1e-4, err
    print("OK", err)
    """
)


def test_grouped_moe_mesh_invariance(tmp_path):
    script = tmp_path / "moe_sharded.py"
    script.write_text(SCRIPT)
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, str(script)], env=env, cwd=os.getcwd(),
        capture_output=True, text=True, timeout=300,
    )
    assert res.returncode == 0, res.stderr[-2500:]
    assert "OK" in res.stdout
