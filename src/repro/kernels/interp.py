"""Where the package's Pallas kernels run in interpret mode.

A kernel's ``interpret=None`` default resolves to interpret mode only on
the CPU backend, where Mosaic cannot run; on a TPU the same
``pallas_call`` lowers to Mosaic. ``REPRO_PALLAS_INTERPRET=1`` forces
interpret mode on regardless of the ``interpret=`` argument, so CPU CI
exercises the kernel path of the oracle tests instead of only the jnp
reference.
"""
from __future__ import annotations

import os

import jax


def force_interpret() -> bool:
    """True when the environment pins interpret mode on."""
    return os.environ.get("REPRO_PALLAS_INTERPRET", "") not in ("", "0")


def resolve(interpret: bool | None) -> bool:
    """The effective interpret flag for a ``pallas_call`` site."""
    if force_interpret():
        return True
    if interpret is None:
        return jax.default_backend() == "cpu"
    return bool(interpret)
