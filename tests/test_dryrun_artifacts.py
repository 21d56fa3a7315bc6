"""Validate collected multi-pod dry-run artifacts (skips if not yet run).

The dry-run itself needs 512 fake devices and must run as its own process:
  XLA_FLAGS=--xla_force_host_platform_device_count=512 JAX_PLATFORMS=cpu \
      PYTHONPATH=src python -m repro.launch.dryrun
"""
import glob
import json
import os

import pytest

from repro.configs import ARCH_IDS, get_config
from repro.launch.steps import applicable_shapes

OUT = "results/dryrun"

pytestmark = pytest.mark.skipif(
    not glob.glob(os.path.join(OUT, "*.json")),
    reason="dry-run artifacts not collected (run repro.launch.dryrun)",
)


def _cells(mesh):
    out = {}
    for f in glob.glob(os.path.join(OUT, f"*__{mesh}.json")):
        r = json.load(open(f))
        if r.get("tag"):
            continue  # hillclimb variants tracked separately
        out[(r["arch"], r["shape"])] = r
    return out


@pytest.mark.parametrize("mesh", ["single", "multi"])
def test_every_applicable_cell_compiled(mesh):
    cells = _cells(mesh)
    missing, failed = [], []
    for arch in ARCH_IDS:
        for shape in applicable_shapes(get_config(arch)):
            r = cells.get((arch, shape))
            if r is None:
                missing.append((arch, shape))
            elif r["status"] != "ok":
                failed.append((arch, shape, r.get("error")))
    assert not missing, f"cells never dry-run: {missing}"
    assert not failed, f"cells failed to compile: {failed}"


def test_long500k_only_for_subquadratic():
    cells = _cells("single")
    for arch in ARCH_IDS:
        cfg = get_config(arch)
        has = (arch, "long_500k") in cells
        assert has == cfg.sub_quadratic, (arch, has, cfg.sub_quadratic)


def test_roofline_terms_present_and_positive():
    for (arch, shape), r in _cells("single").items():
        t = r["terms"]
        assert t["compute_s"] > 0 or shape.startswith("decode") or shape == "long_500k"
        assert t["memory_s"] > 0
        assert t["dominant"] in ("compute", "memory", "collective")


def test_multi_pod_uses_512_chips():
    for r in _cells("multi").values():
        assert r["chips"] == 512
    for r in _cells("single").values():
        assert r["chips"] == 256
