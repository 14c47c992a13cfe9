"""The one interpret-mode switch for every Pallas kernel in this package.

A kernel runs compiled (Mosaic) when its arrays live on a TPU and in
interpret mode everywhere else — the CPU test suite.  Each kernel's
``interpret`` argument defaults to ``None``, which resolves here, so no
caller (serving engine, executor, model code) can reach interpret mode on
a TPU by forgetting to pass the flag.
"""
from __future__ import annotations

from typing import Optional

import jax


def platform_of(x=None) -> str:
    """Platform of the device ``x`` lives on.  Tracers and host arrays
    (numpy) carry no device: they take the default backend, which is
    where jit places the computation."""
    if isinstance(x, jax.Array) and not isinstance(x, jax.core.Tracer):
        return next(iter(x.devices())).platform
    return jax.default_backend()


def interpret_mode(x=None, interpret: Optional[bool] = None) -> bool:
    """Resolve a kernel's ``interpret`` flag: an explicit bool wins (the
    compile-only tests force ``False``); ``None`` means compiled on TPU,
    interpreted on any other platform."""
    if interpret is not None:
        return bool(interpret)
    return platform_of(x) != "tpu"
