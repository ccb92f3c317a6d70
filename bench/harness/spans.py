"""The harness's own host spans and counters.

Spans are written into the profiler's trace as ``TraceAnnotation``s only
in a traced run (``--trace 1``); untraced runs keep just the counters, so
the end-to-end window carries no tracing cost.  Span names the trace
reduction attributes device time and idle gaps to: ``kernel <sig>``,
``reference``, ``skip bookkeeping``, ``campaign``, ``train step``,
``batch fetch``, ``bench window``.
"""

from __future__ import annotations

import contextlib
from collections import Counter


class Recorder:
    def __init__(self, traced: bool):
        self.traced = traced
        self.counters: Counter = Counter()
        #: span name -> (FLOPs, bytes) a kernel span's call needs
        self.kernel_costs: dict = {}
        self._open = None

    def span(self, name: str):
        if not self.traced:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def switch(self, name):
        """Close the phase span left open by the last ``switch`` and open
        ``name`` (None closes only): for phases whose end is the start of
        the next, such as a reference pass followed by trials."""
        if not self.traced:
            return
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None
        if name is not None:
            self._open = self.span(name)
            self._open.__enter__()
