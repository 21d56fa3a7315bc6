"""The program's host spans (``repro.runtime.spans``) and the named scopes of
the decide program's stages.

Spans: off, a profiled ``ServeSession.step`` writes no ``r2e.`` event; on,
each step writes one ``r2e.step`` holding one ``r2e.launch``, and the
step's device operations (the CPU client's op lines, matched to the launch
by the profiler's ``run_id``) start after that launch starts, on the same
clock.  Scopes: every fusion, loop, conditional and custom call that the
compiled decide program carries from the program's own code lies under one
of the five stage scopes.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cost_model import SystemConfig
from repro.core.features import feature_dim
from repro.core.gating import GateConfig, gate_specs
from repro.models.params import init_params
from repro.runtime import spans
from repro.serving import session as session_mod
from repro.serving.policy import Observation, make_policy

M = 16
SCOPES = ("r2e.gate", "r2e.stage1", "r2e.ccg", "r2e.consistency",
          "r2e.repair")
SCOPED_OPS = ("fusion", "while", "conditional", "custom-call")


def _session():
    gcfg = GateConfig(d_feature=feature_dim())
    policy = make_policy("r2evid", SystemConfig(), gate_cfg=gcfg,
                         gate_params=init_params(gate_specs(gcfg),
                                                 jax.random.PRNGKey(0)))
    return session_mod.ServeSession(policy, M)


def _obs(k: int):
    rng = np.random.default_rng(k)
    # a 0.05x uplink binds C6 at this size, so the repair loop runs
    return Observation(
        z=jnp.asarray(rng.uniform(0.02, 1.0, M), jnp.float32),
        aq=jnp.asarray(rng.uniform(0.5, 0.8, M), jnp.float32),
        dx=jnp.asarray(rng.normal(size=(M, feature_dim())), jnp.float32),
        bw_scale=jnp.float32(0.05))


def _profiled_steps(tmp_path, n: int):
    """Run n steps (after one warm step) inside a profiled window; returns
    the host plane's events and the decide program's operation events."""
    from jax.profiler import ProfileData

    sess = _session()
    jax.block_until_ready(sess.step(_obs(0)))
    jax.profiler.start_trace(str(tmp_path))
    try:
        for k in range(n):
            jax.block_until_ready(sess.step(_obs(k + 1)))
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True))[-1]
    host, ops = [], []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                st = dict(e.stats)
                if st.get("hlo_module", "").startswith("jit__decide_step"):
                    ops.append((e.start_ns, st["run_id"]))
                else:
                    host.append((e.name, e.start_ns, e.end_ns, st))
    return host, ops


def test_off_span_is_one_shared_noop():
    assert not spans.enabled()
    assert spans.span("r2e.step") is spans.span("r2e.launch")
    spans.enable(True)
    try:
        assert spans.enabled()
        assert spans.span("r2e.step") is not spans.span("r2e.step")
    finally:
        spans.enable(False)
    assert not spans.enabled()


def test_spans_off_write_no_program_event(tmp_path):
    host, ops = _profiled_steps(tmp_path, 2)
    assert ops, "no operation of the decide program in the trace"
    assert not [h for h in host if h[0].startswith("r2e.")]


def test_each_step_holds_one_launch_before_its_device_ops(tmp_path):
    spans.enable(True)
    try:
        host, ops = _profiled_steps(tmp_path, 3)
    finally:
        spans.enable(False)
    steps = sorted(h for h in host if h[0] == "r2e.step")
    launches = sorted(h for h in host if h[0] == "r2e.launch")
    assert len(steps) == len(launches) == 3
    runs = [h for h in host if "run_id" in h[3]]
    for (_, s0, s1, _), (_, l0, l1, _) in zip(steps, launches):
        assert s0 <= l0 <= l1 <= s1
        # the launch's own execution: the runtime event inside it that
        # carries the run id, and the device operations of that run
        ids = {st["run_id"] for _, a, b, st in runs if l0 <= a <= b <= l1}
        assert len(ids) == 1
        run_ops = [t for t, rid in ops if rid in ids]
        assert run_ops and min(run_ops) > l0


def _hlo_instructions(text: str):
    """(name, opcode, op_name or None) of every instruction of an HLO
    module's text."""
    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = ", line)
        if not m:
            continue
        rest = re.sub(r"/\*.*?\*/", "", line[m.end():])
        if rest.startswith("("):            # a tuple type: skip to its end
            depth = 0
            for i, ch in enumerate(rest):
                depth += {"(": 1, ")": -1}.get(ch, 0)
                if depth == 0:
                    break
            rest = rest[i + 1:]
        else:
            rest = rest.split(" ", 1)[1]
        opcode = re.match(r"\s*([\w-]+)\(", rest)
        op_name = re.search(r'op_name="([^"]*)"', rest)
        if opcode:
            out.append((m.group(1), opcode.group(1),
                        op_name.group(1) if op_name else None))
    return out


def unscoped_program_ops(text: str):
    """The fusions, loops, conditionals and custom calls that carry the
    program's own metadata (``jit(...)/...``) but lie under no stage scope.
    Operations the compiler makes (layout copies, wrapped reductions) carry
    no op_name, and no named scope can reach them: a trace reduction counts
    their time as unscoped."""
    return [(name, op, on) for name, op, on in _hlo_instructions(text)
            if op in SCOPED_OPS and on and on.startswith("jit(")
            and not set(on.split("/")) & set(SCOPES)]


def test_decide_program_ops_lie_under_stage_scopes():
    sess = _session()
    text = session_mod._decide_step.lower(
        sess.policy, sess.state, _obs(0)).compile().as_text()
    ops = _hlo_instructions(text)
    seen = {s for _, op, on in ops if op in SCOPED_OPS and on
            for s in on.split("/") if s in SCOPES}
    assert seen == set(SCOPES)
    assert any(op == "while" for _, op, _ in ops)
    assert unscoped_program_ops(text) == []
