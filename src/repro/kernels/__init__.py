"""Pallas kernels: the SneakPeek k-NN evidence and Eq. 2 utility kernels,
plus the attention and SSD kernels of the model layers.

Each ``<name>/ops.py`` wrapper runs its kernel through ``for_platform``,
the one place that decides Pallas interpret mode: the kernel compiles
through Mosaic for whatever platform the computation is lowered for, and
runs in interpret mode only where that platform is the CPU.
"""
from __future__ import annotations

import jax

__all__ = ["for_platform"]


def for_platform(kernel, *args):
    """``kernel(*args, interpret=...)`` with interpret mode chosen when the
    surrounding computation is lowered: True for the CPU, False for an
    accelerator.  Only the branch of the lowering platform is compiled."""
    return jax.lax.platform_dependent(
        *args,
        cpu=lambda *a: kernel(*a, interpret=True),
        default=lambda *a: kernel(*a, interpret=False),
    )
