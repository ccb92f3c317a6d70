"""Dispatch wrappers: the Pallas kernels, compiled for the TPU or run by the
Pallas interpreter when the caller passes ``interpret=True``.

Nothing falls back on its own: on a backend other than the TPU a call
without ``interpret=True`` raises, so a CPU run can never pass for a
kernel measurement.  Tests sweep shapes/dtypes asserting allclose against
``ref.py``.
"""

from __future__ import annotations

import jax

from . import flash_attention as _flash
from .matmul import matmul_pallas
from .rmsnorm import rmsnorm_pallas


def _check_backend(interpret: bool) -> bool:
    backend = jax.default_backend()
    if not interpret and backend != "tpu":
        raise RuntimeError(
            f"Pallas kernels compile for the TPU only, and the backend is "
            f"{backend!r}: pass interpret=True to run them in the Pallas "
            f"interpreter")
    return interpret


def matmul(a, b, *, interpret: bool = False, **kw):
    return matmul_pallas(a, b, interpret=_check_backend(interpret), **kw)


def rmsnorm(x, w, *, eps: float = 1e-5, interpret: bool = False, **kw):
    return rmsnorm_pallas(x, w, eps=eps, interpret=_check_backend(interpret),
                          **kw)


def flash_attention(q, k, v, *, interpret: bool = False, **kw):
    """Causal self-attention with its own backward, in the model's layout:
    q (B, S, H, d), k/v (B, S, KVH, d) -> (B, S, H, d)."""
    return _flash.flash_attention(q, k, v,
                                  interpret=_check_backend(interpret), **kw)
