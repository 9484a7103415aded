"""The one place that decides how the Pallas kernels run.

On a TPU backend every kernel compiles through Mosaic. On any other
backend (the CPU test suite) it runs in Pallas interpret mode. Each
kernel entry point takes ``interpret=None`` and resolves it here, so a
caller that must know which mode it got (``chip_smoke.py`` asserts it)
asks :func:`use_interpret` instead of re-deriving the rule.
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["use_interpret"]


def use_interpret(interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` argument: an explicit bool wins,
    ``None`` means interpret mode exactly when the backend is not a TPU."""
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"
