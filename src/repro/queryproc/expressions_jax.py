"""``compile_expr`` retargeted at ``jax.numpy`` — the tensor-backend twin.

``compile_expr_jnp(e)`` lowers the same ``Expr`` tree that
``expressions.compile_expr`` lowers, into a closure over a dict of
**jax** arrays (or tracers): same tree walk, same association order, the
numpy ufuncs swapped for their ``jax.numpy`` twins. Under x64
(``jax.enable_x64(True)``) the results match the numpy closure
bitwise on the CPU backend — ``compiler/tensorize.py`` relies on this to evaluate residual
Filter predicates inside a ``jax.jit``-traced program, and
``tests/test_tensorize.py`` pins the equivalence on random columns.

Kept dependency-light on purpose: importing this module does not import
jax (the closures do, lazily), so the numpy-only paths never pay for it.
"""
from __future__ import annotations

from typing import Any, Callable, Dict

import numpy as np

from repro.queryproc.expressions import And, Cmp, Col, Expr, In, Or

# filled on first compile; maps the same op tokens _OPS maps for numpy
_JOPS: Dict[str, Callable] = {}


def _jnp():
    import jax.numpy as jnp
    if not _JOPS:
        _JOPS.update({"<=": jnp.less_equal, "<": jnp.less,
                      ">=": jnp.greater_equal, ">": jnp.greater,
                      "==": jnp.equal})
    return jnp


def compile_expr_jnp(expr: Expr) -> Callable[[Dict[str, Any]], Any]:
    """Lower the tree once into a jax.numpy closure over a column dict.

    Structurally identical to ``expressions.compile_expr`` — Cmp leaves
    bind the ufunc and operands, In binds a membership test, And/Or
    compose with ``&``/``|`` in the same association order — so the two
    closures compute the same boolean mask on the same inputs."""
    jnp = _jnp()
    if isinstance(expr, Cmp):
        op = _JOPS[expr.op]
        name = expr.col.name
        if isinstance(expr.value, Col):
            rname = expr.value.name
            return lambda cols: op(cols[name], cols[rname])
        v = expr.value
        return lambda cols: op(cols[name], v)
    if isinstance(expr, In):
        name = expr.col.name
        vals = jnp.asarray(np.asarray(expr.values))
        return lambda cols: jnp.isin(cols[name], vals)
    if isinstance(expr, And):
        lf, rf = compile_expr_jnp(expr.left), compile_expr_jnp(expr.right)
        return lambda cols: lf(cols) & rf(cols)
    if isinstance(expr, Or):
        lf, rf = compile_expr_jnp(expr.left), compile_expr_jnp(expr.right)
        return lambda cols: lf(cols) | rf(cols)
    raise TypeError(expr)
