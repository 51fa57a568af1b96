"""Matmul and convolution FLOPs of a function as written, from its jaxpr.

A copy of the arithmetic in ``fedml_tpu/utils/flops.py`` (2*M*N*K per
matmul, 2*|out|*Cin/g*|kernel| per convolution, scan times its length, cond
by its costliest branch), kept here so that no later PR can move the
yardstick. The benchmark evaluates it on the plain reference's loss and
gradient for ONE real sample or sequence: the work the algorithm requires,
with no padded step and nothing recomputed."""

from __future__ import annotations

import jax


def _prod(xs) -> int:
    out = 1
    for x in xs:
        out *= int(x)
    return out


def _dot_general_flops(eqn) -> float:
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    lhs = eqn.invars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    batch = _prod(lhs[i] for i in lb)
    k = _prod(lhs[i] for i in lc)
    m = _prod(lhs[i] for i in range(len(lhs)) if i not in lc and i not in lb)
    n = _prod(rhs[i] for i in range(len(rhs)) if i not in rc and i not in rb)
    return 2.0 * batch * m * n * k


def _conv_flops(eqn) -> float:
    out = eqn.outvars[0].aval.shape
    rhs = eqn.invars[1].aval.shape
    dn = eqn.params["dimension_numbers"]
    out_spatial = _prod(out[i] for i in dn.out_spec[2:])
    out_batch = out[dn.out_spec[0]]
    out_ch = out[dn.out_spec[1]]
    kernel_spatial = _prod(rhs[i] for i in dn.rhs_spec[2:])
    cin_per_group = rhs[dn.rhs_spec[1]]
    return 2.0 * out_batch * out_spatial * out_ch * cin_per_group * kernel_spatial


_SUBJAXPR_KEYS = ("jaxpr", "call_jaxpr", "fun_jaxpr")


def jaxpr_flops(jaxpr) -> float:
    """Matmul + convolution FLOPs of one execution of ``jaxpr``."""
    j = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    total = 0.0
    for eqn in j.eqns:
        name = eqn.primitive.name
        if name == "dot_general":
            total += _dot_general_flops(eqn)
        elif name == "conv_general_dilated":
            total += _conv_flops(eqn)
        elif name == "scan":
            total += float(eqn.params["length"]) * jaxpr_flops(eqn.params["jaxpr"])
        elif name == "while":
            raise ValueError("a while loop has no static trip count to count")
        elif name == "cond":
            total += max(jaxpr_flops(b) for b in eqn.params["branches"])
        else:
            for key in _SUBJAXPR_KEYS:
                sub = eqn.params.get(key)
                if sub is not None:
                    total += jaxpr_flops(sub)
                    break
    return total


def fn_flops(fn, *args) -> float:
    """FLOPs of one call of ``fn`` at these argument shapes (shapes only:
    ``jax.ShapeDtypeStruct`` arguments are enough)."""
    return jaxpr_flops(jax.make_jaxpr(fn)(*args))
