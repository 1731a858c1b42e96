"""Shared kernel runtime policy: one place that decides interpret mode.

Every public kernel wrapper historically made its own call — some
hardcoded ``interpret=True``, others probed the backend — so moving a
caller between wrappers could silently change whether the Mosaic
lowering ran. All wrappers now resolve through
:func:`default_interpret`: ``None`` means auto-select (interpret
everywhere except a real TPU backend), an explicit bool overrides (the
microbench uses this to force-interpret on device for parity checks).
:func:`default_use_kernel` resolves ``SearchParams.use_kernel`` the
same way: ``None`` takes the query-weight lookup that suits the
backend (the pair-match kernels on a TPU, the XLA gather elsewhere).
"""
from __future__ import annotations

import jax


def on_tpu() -> bool:
    """True when the default jax backend is a real TPU."""
    return jax.default_backend() == "tpu"


def default_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret`` knob: ``None`` -> auto (not on TPU)."""
    return not on_tpu() if interpret is None else bool(interpret)


def default_use_kernel(use_kernel: bool | None = None) -> bool:
    """Resolve ``SearchParams.use_kernel``: ``None`` -> auto (the
    fuse-level-0 kernels on TPU, whose SMEM pair-match replaces the
    element gather XLA runs one element at a time there; the XLA
    gather elsewhere, where it is cheap)."""
    return on_tpu() if use_kernel is None else bool(use_kernel)


__all__ = ["on_tpu", "default_interpret", "default_use_kernel"]
