"""The trace reduction on a small trace recorded on the CPU: its jitted
operations stand in for a device's."""
import glob

import pytest

import xplane

CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLA",
           module_line=r"^$", host_plane=r"^/host:CPU$")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((192, 192), jnp.float32)
    f(x).block_until_ready()
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.round"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait_due"):
                sum(range(20000))
    jax.profiler.stop_trace()
    return sorted(glob.glob(f"{out}/**/*.xplane.pb", recursive=True))[-1]


def test_busy_union_and_idle_share(recorded):
    t = xplane.Trace(recorded, **CPU)
    assert t.n_devices == 1
    ops = t.ops[0]
    assert ops, "no operation events on the CPU client threads"
    want = xplane.covered((e.start, e.end) for e in ops) * 1e-9
    assert t.busy_s == pytest.approx(want)
    assert 0 < t.busy_s < t.window_s
    assert t.idle_share == pytest.approx(1 - t.busy_s / t.window_s)
    assert t.span_count("bench.round") == 3
    # every operation lies inside the traced window
    assert all(t.lo <= e.start <= e.end <= t.hi for e in ops)


def test_name_patterns_and_missing_ones(recorded):
    t = xplane.Trace(recorded, **CPU)
    name = t.top_ops(1)[0][0]
    assert t.time_s(name) > 0 and t.count(name) >= 1
    assert t.time_s("no_such_kernel") is None
    assert t.time_s("no_such_program", modules=True) is None
    gaps = t.idle_gaps(3)
    assert gaps and all(s > 0 for _, s in gaps)
    assert {label for label, _ in gaps} <= {"bench.round", "bench.wait_due",
                                            "none"}


def test_union_of_overlapping_intervals():
    assert xplane.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert xplane.covered([(0, 2), (1, 3), (10, 11)]) == 4
