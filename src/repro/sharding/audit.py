"""Structural cross-device communication audit over jaxprs.

The hierarchical sharded serve path promises that NO (M, ...) array crosses
devices inside the per-round scan body — only O(n_devices) scalar stats.
That property is cheap to regress silently (one stray ``all_gather`` and the
fleet-scale story is gone), so instead of trusting the code we *measure* the
jaxpr: :func:`iter_collectives` walks every equation (recursing through
scan/cond/pjit/shard_map sub-jaxprs) and reports each collective primitive
with its largest operand size and whether it sits inside a ``scan`` body.
``tests/test_hierarchical.py`` asserts the invariant against it in CI.
"""
from __future__ import annotations

import math

import jax
from jax.extend.core import ClosedJaxpr, Jaxpr

#: primitive-name fragments that imply cross-device traffic under shard_map
COLLECTIVE_PRIMS = ("all_gather", "all_to_all", "psum", "pmax", "pmin",
                    "ppermute", "reduce_scatter", "pbroadcast")
#: loop primitives whose bodies are "the round body" for the audit
_LOOP_PRIMS = ("scan", "while")


def _sub_jaxprs(params):
    """Yield every (Closed)Jaxpr reachable from an eqn's params."""
    for val in params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, Jaxpr):
                yield v


def iter_collectives(jaxpr, _in_loop=False):
    """Yield ``(prim_name, max_operand_elems, in_loop)`` for every collective
    equation reachable from ``jaxpr`` (a ``Jaxpr`` or ``ClosedJaxpr``).

    ``max_operand_elems`` is the element count of the largest input operand —
    the quantity that must stay O(n_devices) inside the hierarchical round
    body.  ``in_loop`` marks equations nested (at any depth) inside a
    ``scan``/``while`` body, i.e. executed every serving round.
    """
    if isinstance(jaxpr, ClosedJaxpr):
        jaxpr = jaxpr.jaxpr
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if any(frag in name for frag in COLLECTIVE_PRIMS):
            size = 0
            for var in eqn.invars:
                aval = getattr(var, "aval", None)
                if aval is not None and getattr(aval, "shape", None) is not None:
                    size = max(size, int(math.prod(aval.shape)))
            yield name, size, _in_loop
        inner = _in_loop or any(frag in name for frag in _LOOP_PRIMS)
        for sub in _sub_jaxprs(eqn.params):
            yield from iter_collectives(sub, inner)


def collective_footprint(fn, *args, **kwargs):
    """Trace ``fn(*args, **kwargs)`` and return its collectives as a list of
    ``(prim_name, max_operand_elems, in_loop)`` tuples."""
    jaxpr = jax.make_jaxpr(fn)(*args, **kwargs)
    return list(iter_collectives(jaxpr))


def max_loop_collective_elems(fn, *args, **kwargs):
    """The largest collective operand (in elements) executed inside any loop
    body of ``fn`` — 0 when loop bodies are collective-free.  The number the
    hierarchical serve path bounds by O(n_devices)."""
    return max((size for _, size, in_loop in
                collective_footprint(fn, *args, **kwargs) if in_loop),
               default=0)
