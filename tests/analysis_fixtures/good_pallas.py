"""reprolint negative fixture: a well-formed guarded kernel wrapper."""
import jax
from jax.experimental import pallas as pl

from repro.core.policy import resolve_interpret


def _body(x_ref, o_ref):
    o_ref[...] = x_ref[...]


def good_kernel(x, interpret):
    m, n = x.shape
    bm, bn = 8, 16
    if m % bm or n % bn:
        raise ValueError("shapes must tile evenly")
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        _body,
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=interpret,  # policy-routed: callers pass KernelPolicy.interpret
    )(x)


def good_kernel_platform_default(x, interpret=None):
    return pl.pallas_call(
        _body,
        out_shape=jax.ShapeDtypeStruct(x.shape, x.dtype),
        interpret=resolve_interpret(interpret),  # the one platform resolver
    )(x)
