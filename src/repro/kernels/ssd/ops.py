"""Jitted SSD wrapper matching the model's mixer inputs."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import for_platform
from repro.kernels.ssd.kernel import ssd_pallas
from repro.kernels.ssd.ref import ssd_ref

__all__ = ["ssd"]


@functools.partial(jax.jit, static_argnames=("chunk", "use_kernel"))
def ssd(x, dt, a_log, bm, cm, chunk: int = 128, use_kernel: bool = True):
    """Model-facing API: x (B,S,H,P); dt (B,S,H) post-softplus; a_log (H,);
    bm/cm (B,S,N) (ngroups=1).  Returns (y, final_state)."""
    a = -jnp.exp(a_log.astype(jnp.float32))
    dA = dt * a[None, None, :]
    xdt = x * dt[..., None]
    if use_kernel:
        return for_platform(functools.partial(ssd_pallas, chunk=chunk), xdt, dA, bm, cm)
    return ssd_ref(xdt, dA, bm, cm, chunk=chunk)
