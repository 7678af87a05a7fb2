"""Jaxpr type tests shared by every analysis pass (audit, contract,
shard_check, absint), written once against the public ``jax.extend.core``
names of the installed JAX, so an API change is repaired here only."""
from __future__ import annotations

from jax.extend import core as _core

ClosedJaxpr = _core.ClosedJaxpr
Jaxpr = _core.Jaxpr


def is_literal(atom) -> bool:
    """A constant operand inlined into an equation."""
    return isinstance(atom, _core.Literal)


def is_drop_var(atom) -> bool:
    """An equation output nothing reads (printed ``_``); ``jax.extend``
    does not export its class, so the test goes by name."""
    return type(atom).__name__ == "DropVar"


def open_jaxpr(jaxpr):
    """The Jaxpr inside a ClosedJaxpr; a Jaxpr passes through."""
    return jaxpr.jaxpr if isinstance(jaxpr, _core.ClosedJaxpr) else jaxpr


def sub_jaxprs(eqn):
    """Every Jaxpr an equation's params hold: scan, while (cond/body),
    cond branches, pjit, shard_map, remat, custom_jvp/vjp, pallas_call."""
    for p in eqn.params.values():
        for item in (p if isinstance(p, (tuple, list)) else (p,)):
            if isinstance(item, (_core.ClosedJaxpr, _core.Jaxpr)):
                yield open_jaxpr(item)
