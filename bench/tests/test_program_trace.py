"""What the program's spans and scopes add to the trace reduction, on a
small trace recorded on the CPU: a jitted function whose second half lies
under a named scope stands in for the decide program, an ``r2e.launch``
span for the program's span around its launch."""
import glob
import json

import pytest

import program_trace
import xplane

CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLA",
           module_line=r"^$", host_plane=r"^/host:CPU$")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    import jax
    import jax.numpy as jnp

    def f(x):
        y = jnp.tanh(x @ x)
        with jax.named_scope("r2e.repair"):
            return jax.lax.fori_loop(0, 4, lambda i, c: c * 0.5 + 1, y).sum()

    f = jax.jit(f)
    x = jnp.ones((192, 192), jnp.float32)
    f(x).block_until_ready()
    hlo = f.lower(x).compile().as_text()
    out = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(out))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.round"):
                with jax.profiler.TraceAnnotation("r2e.launch"):
                    y = f(x)
                y.block_until_ready()
            with jax.profiler.TraceAnnotation("bench.wait_due"):
                sum(range(20000))
    jax.profiler.stop_trace()
    path = sorted(glob.glob(f"{out}/**/*.xplane.pb", recursive=True))[-1]
    return path, hlo


def test_harness_trace_keeps_only_its_own_spans(recorded):
    t = xplane.Trace(recorded[0], **CPU)
    assert t.spans and all(s.name.startswith("bench.") for s in t.spans)
    assert t.span_count("r2e.launch") == 0 and t.span_s("r2e.launch") is None


def test_program_spans_and_the_runtime_inside_them(recorded):
    t = program_trace.ProgramTrace(recorded[0], hlo_text=recorded[1], **CPU)
    assert [s.name for s in t.program_spans] == ["r2e.launch"] * 3
    assert t.span_count("r2e.launch") == 3 and t.span_count("bench.round") == 3
    launch = t.span_s("r2e.launch")
    execute = t.runtime_in("r2e.launch", r"Execute")
    assert 0 < execute < launch
    assert t.runtime_in("r2e.launch", r"no_such_event") is None
    assert t.runtime_in("r2e.no_such_span", r"Execute") is None
    split = t.launch_split()
    assert split["execute"] > 0 and split["python"] > 0
    assert sum(split.values()) == pytest.approx(launch)
    assert t.launch_split("r2e.no_such_span") is None
    over = t.host_events_in("r2e.launch")
    executes = [sec for _, ev, sec in over if ev.endswith("::Execute")]
    assert executes and all(0 < sec <= launch for sec in executes)
    assert "r2e.launch" not in {ev for _, ev, _ in over}
    assert t.host_events_in("r2e.no_such_span") == []


def test_scope_time_attributes_the_scoped_ops(recorded):
    path, hlo = recorded
    t = program_trace.ProgramTrace(path, hlo_text=hlo, **CPU)
    scoped = t.scope_time_s("r2e.repair")
    assert 0 < scoped < t.busy_s
    assert t.scope_time_s(None) == pytest.approx(scoped)
    assert t.scope_time_s("r2e.gate") is None
    assert t.scope_time_s("no_such_scope") is None
    # without a map, no op is attributed to a scope: never a guess
    assert program_trace.ProgramTrace(path, **CPU).scope_time_s(
        "r2e.repair") is None


def test_idle_gaps_carry_program_labels(recorded):
    t = program_trace.ProgramTrace(recorded[0], **CPU)
    labels = {label for label, _ in t.idle_gaps(20)}
    assert labels <= {"bench.round", "bench.wait_due", "r2e.launch", "none"}
    idle = t.idle_under_s("r2e.launch")
    assert 0 < idle <= t.span_s("r2e.launch")
    assert t.idle_under_s("no_such_span") is None


def test_metrics_and_report_line(recorded):
    t = program_trace.ProgramTrace(recorded[0], hlo_text=recorded[1], **CPU)
    got = program_trace.metrics(t)
    assert {"host_us_per_round.launch", "device_idle_pct.route.launch",
            "device_us_per_round.repair"} <= set(got)
    assert got["host_us_per_round.launch"] == pytest.approx(
        t.span_s("r2e.launch") * 1e6 / 3)
    assert 0 < got["device_idle_pct.route.launch"] < 100
    line = program_trace.report(t)
    assert "r2e.repair" in line and "host us per round in r2e.launch" in line


def test_breakdown_of_a_small_router_cell(tmp_path, capsys):
    import breakdown
    import small

    _, cfg, mix = small.spec("fleet4096.congested")
    got = breakdown.one_seed(cfg, dict(mix, trace_seconds=0.3), 2**33 + 5,
                             0.3, tmp_path, **CPU)
    json.dumps(got)
    assert set(got["round_ms_p95"]) == {"off", "on", "traced_off",
                                        "traced_on"}
    assert all(len(v) == 2 for v in got["round_ms_p95"].values())
    m = got["metrics"]
    assert m["host_us_per_round.launch"] > 0
    assert m["device_us_per_round.repair"] > 0
    assert got["rounds_traced"] > 0
    assert got["idle_us_per_round"]["r2e.launch"] > 0
    assert all(label == "none" or label.startswith(("bench.", "r2e."))
               for label, _ in got["idle_gaps"])
    assert "r2e.gate" in capsys.readouterr().err
