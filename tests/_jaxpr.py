"""Shared jaxpr walker for the structural (graph-shape) tests."""
from jax.extend.core import ClosedJaxpr, Jaxpr


def iter_eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the sub-jaxprs nested in its
    params (pjit, scan, while, cond bodies …), depth first."""
    for eqn in jaxpr.eqns:
        yield eqn
        for val in eqn.params.values():
            for v in (val if isinstance(val, (list, tuple)) else (val,)):
                if isinstance(v, ClosedJaxpr):
                    yield from iter_eqns(v.jaxpr)
                elif isinstance(v, Jaxpr):
                    yield from iter_eqns(v)


def jaxpr_shapes(jaxpr):
    """Every intermediate array shape in a jaxpr, nested sub-jaxprs
    included (wherever a gathered or padded tensor could hide)."""
    for eqn in iter_eqns(jaxpr):
        for v in eqn.outvars:
            shape = getattr(getattr(v, "aval", None), "shape", None)
            if shape is not None:
                yield tuple(shape)
