"""Small sizes of the cells that a CPU test run can hold."""
import harness


def _model(c):
    # initializer_range 0.1 at width 64 keeps std * sqrt(width) near the
    # full size's (0.02 * sqrt(1024) = 0.64): attention as sharp, and the
    # layers, not the edge's tied embedding, choosing the tokens
    return dict(c, hidden_size=64, intermediate_size=128,
                num_attention_heads=4, num_key_value_heads=2, head_dim=16,
                num_hidden_layers=2, vocab_size=256, initializer_range=0.1)


def overrides(workload: str) -> dict:
    if workload.startswith("fleet4096"):
        mix = dict(harness.traffic(workload.split(".")[1]), bank_rounds=12)
        if mix.get("telemetry"):
            # 0.05x of the uplink binds at 64 cameras, as 0.3x does at 4096
            mix["telemetry"] = dict(mix["telemetry"], bad_rounds=4,
                                    bad_scale=0.05)
        return {"config": {"cameras": 64}, "traffic": mix}
    cfg = harness.config("edgecloud-qwen")
    dep = dict(cfg["deployment"], resolutions=[360, 1080], fps_options=[10, 50])
    return {"config": {"edge_model": _model(cfg["edge_model"]),
                       "cloud_model": _model(cfg["cloud_model"]),
                       "deployment": dep},
            "traffic": {"cameras": 10, "bank_rounds": 4,
                        "round_period_s": 0.25, "check_segments": 64,
                        "max_warmup_rounds": 2}}


def spec(workload: str) -> tuple:
    """The cell at its small size: (workloads entry, configuration, mix)."""
    config, mix = workload.split(".")
    ov = overrides(workload)
    return ({"name": workload, "config": config, "traffic": mix, "chips": 1},
            dict(harness.config(config), **ov["config"]),
            dict(harness.traffic(mix), **ov["traffic"]))


def run(workload: str, seed: int, seconds: float = 1.0) -> dict:
    return harness.run(workload, seed, seconds, False, small=spec(workload))


def cell(workload: str, seed: int):
    """A small cell built and run once, with its record."""
    _, cfg, mix = spec(workload)
    mod = harness.system(cfg["system"])
    c = mod.Cell(cfg, mix, seed, counter=harness.CompileCounter())
    rec = c.window(1.0, harness.spans(False))
    c.free()
    return mod, c, rec
