"""jit'd public wrapper: Pallas forward (``kernels.interpret_mode()``)
with the FA2 blockwise-recompute backward from jnp_impl."""
from __future__ import annotations

from functools import partial

import jax

from repro.kernels import interpret_mode
from repro.kernels.flash_attention import jnp_impl, kernel


@partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def flash_attention(q, k, v, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 512):
    return kernel.flash_attention_fwd(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, interpret=interpret_mode())


def _fwd(q, k, v, causal, window, block_q, block_k):
    out = flash_attention(q, k, v, causal, window, block_q, block_k)
    # lse recomputed by the jnp backward; save inputs + out
    _, lse = jnp_impl._fwd(q, k, v, causal, window,
                           min(block_q, q.shape[1]), min(block_k, q.shape[1]))
    return out, (q, k, v, out, lse)


def _bwd(causal, window, block_q, block_k, res, dout):
    return jnp_impl._bwd_vjp(causal, window,
                             min(block_q, res[0].shape[1]),
                             min(block_k, res[0].shape[1]), res, dout)


flash_attention.defvjp(_fwd, _bwd)
