# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Kernel layer: Pallas TPU kernels + jnp oracles + backend resolution."""
from __future__ import annotations

import jax


def kernel_backend_available() -> bool:
    """Whether the compiled (Mosaic) kernel path is the right default."""
    return jax.default_backend() == "tpu"


def interpret_default() -> bool:
    """Kernel mode when a caller passes ``interpret=None``: compiled through
    Mosaic on a TPU, the Pallas interpreter everywhere else."""
    return not kernel_backend_available()


def resolve_use_kernel(mode: "bool | str") -> bool:
    """Resolve a tri-state kernel switch to a concrete bool.

    ``True``/``False`` are taken literally (``True`` on CPU runs the kernels
    in interpret mode -- the parity-test configuration).  ``"auto"`` selects
    the Pallas path on TPU and the jnp path everywhere else, so production
    entry points (AQPEngine/AQPSession) can default to the fast path without
    dragging interpret-mode kernels into CPU serving.
    """
    if isinstance(mode, str):
        if mode == "auto":
            return kernel_backend_available()
        raise ValueError(f"use_kernel must be True, False or 'auto'; got {mode!r}")
    return bool(mode)
