"""The routing kernels compile for a TPU v5e chip that is described, not
attached: Mosaic's refusals (tiling, layouts, unsupported ops) surface here
on the CPU, long before a chip run.  Each case compiles one kernel's ops
entry point with ``force="pallas"`` at fleet size M, at a small M whose
block is the whole array, and at an M the ops wrapper must pad, and checks
the compiled program holds the kernel.

The topology is described inside a fixture (never at import): only one
process at a time may load the TPU library, and every test worker imports
this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.cost_model import SystemConfig
from repro.core.features import feature_dim
from repro.core.gating import GateConfig, gate_specs
from repro.core.robust import RobustProblem
from repro.models.params import init_params


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _kernel_call(name, m, spec):
    """(fn, abstract args) for one kernel's ops entry point at batch m."""
    sys_ = SystemConfig()
    prob = RobustProblem.build(sys_)
    lat = prob.lat
    k = sys_.num_versions
    i32 = jnp.int32
    if name == "temporal_gate":
        from repro.kernels.temporal_gate.ops import gate_cell

        gcfg = GateConfig(d_feature=feature_dim())
        p = jax.eval_shape(lambda: init_params(gate_specs(gcfg),
                                               jax.random.PRNGKey(0)))
        return (lambda dx, h, vol, p: gate_cell(dx, h, vol, p, force="pallas"),
                (spec((m, gcfg.d_feature)), spec((m, gcfg.d_hidden)),
                 spec((m,)), jax.tree_util.tree_map(
                     lambda x: spec(x.shape, x.dtype), p)))
    if name == "ccg_solve":
        from repro.kernels.ccg_solve.ops import ccg_solve

        u_all = prob.poles * lat.u_dev
        return (lambda z, aq, wy: ccg_solve(
                    z, aq, lat.rn_flat, lat.pn_flat, lat.tier_flat,
                    lat.b2_flat, u_all, lat.c1_flat, wy,
                    margin=sys_.acc_margin_robust, num_versions=k,
                    force="pallas"),
                (spec((m,)), spec((m,)), spec((m,), i32)))
    if name == "c6_tail":
        from repro.core.cost_model import fps_norm, res_norm
        from repro.kernels.c6_tail.ops import c6_tail

        nz = sys_.n_res * sys_.n_fps
        return (lambda panel, r, p, v, route, z, thr: c6_tail(
                    panel, r, p, v, route, z, thr, res_norm(sys_),
                    fps_norm(sys_), n_fps=sys_.n_fps, force="pallas"),
                (spec((m, nz)),) + tuple(spec((m,), i32) for _ in range(4))
                + (spec((m,)), spec((m,))))
    if name == "ccg_encode":
        from repro.kernels.ccg_encode.ops import ccg_encode

        return (lambda z, aq: ccg_encode(
                    z, aq, lat.rn_flat, lat.pn_flat, lat.tier_flat,
                    prob.b2_scaled, prob.rec_table,
                    margin=sys_.acc_margin_robust, num_versions=k,
                    force="pallas"),
                (spec((m,)), spec((m,))))
    assert name == "ccg_master"
    from repro.kernels.ccg_master.ops import ccg_master

    n_poles, f = prob.poles.shape[0], lat.n_flat
    return (lambda rec, scen, fs_ok, c1: ccg_master(rec, scen, fs_ok, c1,
                                                    force="pallas"),
            (spec((m, n_poles, f)), spec((m, n_poles)),
             spec((m, f), jnp.bool_), spec((f,))))


@pytest.mark.parametrize("m", [64, 4096, 4100])
@pytest.mark.parametrize("name", ["temporal_gate", "ccg_solve", "c6_tail",
                                  "ccg_encode", "ccg_master"])
def test_routing_kernel_compiles_for_v5e(name, m, one_chip,
                                         no_persistent_cache):
    spec = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    fn, args = _kernel_call(name, m, spec)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), name


@pytest.fixture(scope="module")
def decide_v5e_text(one_chip, no_persistent_cache):
    """The whole decide program at fleet size, compiled as the chip
    compiles it (optimized HLO text)."""
    import dataclasses

    from repro.serving.policy import Observation, make_policy
    from repro.serving.session import _decide_step

    m, d = 4096, feature_dim()
    gcfg = GateConfig(d_feature=d)
    policy = make_policy("r2evid", SystemConfig(), gate_cfg=gcfg,
                         gate_params=init_params(gate_specs(gcfg),
                                                 jax.random.PRNGKey(0)))
    policy = dataclasses.replace(policy, force="pallas")
    obs = Observation(z=jnp.zeros((m,)), aq=jnp.zeros((m,)),
                      dx=jnp.zeros((m, d)), bw_scale=jnp.float32(0.3))
    abstract = lambda tree: jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(jnp.shape(x), jnp.asarray(x).dtype,
                                       sharding=one_chip), tree)
    return _decide_step.lower(abstract(policy), abstract(policy.init(m)),
                              abstract(obs)).compile().as_text()


def test_decide_program_for_v5e_keeps_its_stage_scopes(decide_v5e_text):
    """The whole decide program at fleet size, as the chip compiles it: the
    three routing kernels it runs, and every operation that carries the
    program's metadata under one of the five stage scopes (what a device
    trace of the chip attributes its time by)."""
    from test_spans import unscoped_program_ops

    text = decide_v5e_text
    for kernel in ("%gate_cell", "%ccg_solve", "%c6_tail"):
        assert kernel in text, kernel
    assert unscoped_program_ops(text) == []


def test_decide_program_for_v5e_repair_loop_has_no_gather_or_scatter(
        decide_v5e_text):
    """On the chip every data-dependent gather or scatter is a slow op of
    its own; the C6 repair's passes run none.  The repair's one gather is
    the hoisted route panel, outside its loop."""
    import re

    ops = re.findall(r'= \S+ (gather|scatter)\(.*?op_name="([^"]*)"',
                     decide_v5e_text)
    in_repair = [(kind, name) for kind, name in ops if "r2e.repair" in name]
    assert in_repair == [("gather", "jit(_decide_step)/r2e.repair/"
                                    "r2e.repair/gather")], in_repair
