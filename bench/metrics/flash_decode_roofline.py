"""Kernels: the least time the window's decode attention needs
(``flash_decode`` of the configuration's architecture module, per decode
call and layer) over the device time of the ``flash_decode_paged``
operations in the trace, in %."""
from bench import trace as T
from bench import work

KERNEL = "flash_decode_paged"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = T.op_time_ns(run.trace, KERNEL, *run.trace_window) * 1e-9
    if t <= 0:
        return None
    count = run.arch.flash_decode
    need = sum(work.min_seconds(*count(run.sizes, r.decode_pos), run.peaks)
               for r in run.steps if r.decode_pos) * run.sizes.layers
    return 100.0 * need / t
