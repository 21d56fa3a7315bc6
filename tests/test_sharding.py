"""Sharding rules: divisibility fitting, multi-pod adaptation (property-based)."""
import jax
import numpy as np
import pytest

pytest.importorskip("hypothesis")
import hypothesis.strategies as st
from hypothesis import given, settings
from jax.sharding import PartitionSpec as P

from repro.sharding.rules import SERVE_BASE, TRAIN_BASE, make_rules
from repro.sharding.compat import make_mesh


def _mesh(multi=False):
    # tiny host mesh stands in; axis names are what matter for specs
    n = len(jax.devices())
    if multi:
        return make_mesh((1, 1, n), ("pod", "data", "model"))
    return make_mesh((1, n), ("data", "model"))


def test_rule_tables_cover_all_logical_axes():
    assert set(SERVE_BASE) == set(TRAIN_BASE)


def test_multi_pod_prepends_pod_to_data():
    mesh = _mesh(multi=True)
    rules = make_rules(mesh, "train")
    spec = rules.spec(("batch",))
    assert spec == P(("pod", "data"))


def test_single_pod_has_no_pod_axis():
    mesh = _mesh(multi=False)
    rules = make_rules(mesh, "train")
    for name in TRAIN_BASE:
        ax = rules.mapping[name]
        axes = (ax,) if isinstance(ax, str) else (ax or ())
        assert "pod" not in axes


@settings(max_examples=50, deadline=None)
@given(
    dims=st.lists(st.integers(1, 64), min_size=1, max_size=4),
    axes=st.lists(
        st.sampled_from(["batch", "embed", "vocab", "mlp", "experts", None]),
        min_size=1, max_size=4,
    ),
)
def test_fitted_sharding_always_divides(dims, axes):
    n = min(len(dims), len(axes))
    dims, axes = tuple(dims[:n]), tuple(axes[:n])
    mesh = _mesh()
    rules = make_rules(mesh, "train")
    sh = rules.fitted_sharding(mesh, axes, dims)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for dim, entry in zip(dims, tuple(sh.spec) + (None,) * (len(dims) - len(sh.spec))):
        if entry is None:
            continue
        names = (entry,) if isinstance(entry, str) else entry
        total = int(np.prod([sizes[a] for a in names]))
        assert dim % total == 0, (dim, entry)


def test_no_duplicate_mesh_axes_in_one_spec():
    mesh = _mesh()
    rules = make_rules(mesh, "train")
    spec = rules.spec(("heads_flat", "mlp"))  # both map to "model"
    used = [s for s in spec if s is not None]
    flat = []
    for s in used:
        flat += [s] if isinstance(s, str) else list(s)
    assert len(flat) == len(set(flat)), spec


def test_overrides_apply():
    mesh = _mesh()
    rules = make_rules(mesh, "serve", overrides={"experts": None, "expert_mlp": "model"})
    assert rules.spec(("experts",)) == P(None)
    assert rules.spec(("expert_mlp",)) == P("model")


def test_pad_leading_pads_any_axis():
    """pad_leading(axis=) pads exactly the named axis — the sharded serve
    driver uses axis=1 to pad the stream axis of round-stacked (R, M, ...)
    arrays without the moveaxis round-trip."""
    from repro.sharding.compat import pad_leading

    x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    y = np.asarray(pad_leading(x, 2, axis=1))
    assert y.shape == (2, 5, 4)
    np.testing.assert_array_equal(y[:, :3], x)
    assert (y[:, 3:] == 0).all()
    # default keeps the historical leading-axis behavior
    z = np.asarray(pad_leading(x, 1, value=7.0))
    assert z.shape == (3, 3, 4)
    assert (z[2] == 7.0).all()
