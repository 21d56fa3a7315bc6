"""compressed_allreduce under shard_map on 8 (fake) devices.

Needs its own process: XLA device count locks at first jax init, so the test
spawns a subprocess with --xla_force_host_platform_device_count=8.
"""
import os
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    from repro.sharding.compat import make_mesh
    import jax.numpy as jnp
    import numpy as np
    from repro.sharding.compat import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.train.compression import compressed_allreduce

    mesh = make_mesh((8,), ("data",))
    x = jax.random.normal(jax.random.PRNGKey(0), (8, 64)) * 0.3

    def body(xs):
        return compressed_allreduce(xs[0], "data")[None]

    out = jax.jit(
        shard_map(body, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
    )(x)
    ref = jnp.sum(x, axis=0)
    got = out[0]
    err = float(jnp.max(jnp.abs(got - ref)))
    scale = float(jnp.max(jnp.abs(x))) / 127.0
    assert err <= 8 * scale * 0.51 + 1e-6, (err, scale)
    print("OK", err)
    """
)


@pytest.mark.parametrize("_", [0])
def test_compressed_allreduce_8dev(_, tmp_path):
    script = tmp_path / "collective.py"
    script.write_text(SCRIPT)
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, str(script)], env=env, cwd=os.getcwd(),
        capture_output=True, text=True, timeout=300,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    assert "OK" in res.stdout
