"""A second witness for the program's known fault: the engine with each
admission planned one context token short.

The engine prefills a request's whole context, then decodes its first
token by feeding the context's last token again, at position ``n_ctx``
(``serving/executor.py`` ``decode_inputs``): every answer continues the
context with its last token repeated.  Planning ``n_ctx = len(context)
- 1`` instead makes that first decode step feed the last token at its
own position, which is the continuation the reference computes.  Only
the host's admission plan changes; the device programs are the same.

The benchmark's runs never apply this.  ``bench/readings.py`` and the
CPU tests do, to read what runs of the program's numerics give once
the fault is out of the way (PERF.md, section 6).
"""
from __future__ import annotations

import contextlib
import dataclasses

from repro.serving.scheduler import Scheduler


@contextlib.contextmanager
def admission_one_short():
    """Within the block, every engine plans its admissions so that the
    first decode step feeds the context's last token at its own
    position."""
    inner = Scheduler.plan_admission

    def plan(self, r, qdepth):
        p = inner(self, r, qdepth)
        n = len(r.context_tokens())
        if p.n_ctx == n > 1 and p.match is None:
            p = dataclasses.replace(p, n_ctx=n - 1)
        return p

    Scheduler.plan_admission = plan
    try:
        yield
    finally:
        Scheduler.plan_admission = inner
