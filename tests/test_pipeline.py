"""GPipe pipeline parallelism: multi-(fake-)device correctness + bubble math.

Runs in a subprocess (device count locks at first jax init).
"""
import os
import subprocess
import sys
import textwrap

from repro.sharding.pipeline import bubble_fraction

SCRIPT = textwrap.dedent(
    """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    import jax
    from repro.sharding.compat import make_mesh
    import jax.numpy as jnp
    import numpy as np

    from repro.sharding.pipeline import pipeline, split_stages

    S, LPS, D, M, B = 4, 2, 16, 8, 4      # stages, layers/stage, width, microbatches, mb size
    L = S * LPS
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (L, D, D)) * (D ** -0.5)
    b = jax.random.normal(jax.random.PRNGKey(1), (L, D)) * 0.1
    xs = jax.random.normal(jax.random.PRNGKey(2), (M, B, D))

    def layer(wi, bi, x):
        return jnp.tanh(x @ wi + bi)

    # sequential reference
    ref = xs
    for i in range(L):
        ref = jax.vmap(lambda x: layer(w[i], b[i], x))(ref)

    def stage_fn(params, x):
        ws, bs = params
        def body(x, wb):
            return layer(wb[0], wb[1], x), None
        out, _ = jax.lax.scan(body, x, (ws, bs))
        return out

    mesh = make_mesh((4,), ("stage",))
    stage_params = split_stages((w, b), S)
    fn = pipeline(stage_fn, mesh, axis="stage")
    out = jax.jit(fn)(stage_params, xs)
    err = float(jnp.max(jnp.abs(out - ref)))
    assert err < 1e-5, err
    print("OK", err)
    """
)


def test_pipeline_matches_sequential(tmp_path):
    script = tmp_path / "pp.py"
    script.write_text(SCRIPT)
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run(
        [sys.executable, str(script)], env=env, cwd=os.getcwd(),
        capture_output=True, text=True, timeout=300,
    )
    assert res.returncode == 0, res.stderr[-2500:]
    assert "OK" in res.stdout


def test_bubble_fraction():
    assert bubble_fraction(8, 4) == 3 / 11
    assert bubble_fraction(1, 4) == 3 / 4   # single microbatch: mostly bubble
    assert bubble_fraction(64, 2) < 0.02    # deep microbatching amortizes
