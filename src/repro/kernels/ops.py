"""Dispatch wrappers: the Pallas kernels, compiled for the TPU or run by the
Pallas interpreter when the caller passes ``interpret=True``.

Nothing falls back on its own: on a backend other than the TPU a call
without ``interpret=True`` raises, so a CPU run can never pass for a
kernel measurement.  Tests sweep shapes/dtypes asserting allclose against
``ref.py``.
"""

from __future__ import annotations

import jax

from .flash_attention import flash_attention_pallas
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


def flash_attention(q, k, v, *, causal: bool = True, interpret: bool = False,
                    **kw):
    """(B, Sq, H, d) layout (model-native); transposes into kernel layout."""
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)
    ot = flash_attention_pallas(qt, kt, vt, causal=causal,
                                interpret=_check_backend(interpret), **kw)
    return ot.transpose(0, 2, 1, 3)
