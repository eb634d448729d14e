"""jit'd public wrapper for the SSD kernel with a jnp-recompute backward
(the chunked scan itself is cheap to replay; gradients route through the
oracle implementation, which is numerically identical)."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_mode
from repro.kernels.ssd_scan import kernel, ref


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def ssd(x, dt, A, B_, C_, chunk: int = 128):
    return kernel.ssd_fwd(x, dt, A, B_, C_, chunk=chunk,
                          interpret=interpret_mode())


def _fwd(x, dt, A, B_, C_, chunk):
    out = ssd(x, dt, A, B_, C_, chunk)
    return out, (x, dt, A, B_, C_)


def _bwd(chunk, res, cts):
    x, dt, A, B_, C_ = res
    dy, dstate = cts

    def f(x, dt, A, B_, C_):
        return ref.ssd(x, dt, A, B_, C_, chunk=chunk)

    _, vjp = jax.vjp(f, x, dt, A, B_, C_)
    return vjp((dy, dstate))


ssd.defvjp(_fwd, _bwd)
