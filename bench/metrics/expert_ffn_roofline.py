"""Kernels: the least time the window's routed-expert work needs
(bench/work.py expert_ffn, per call and layer, from each call's
``slot_hist``) over the device time of the ``fused_expert_ffn_pallas``
operations in the trace, in %."""
from bench import trace as T
from bench import work

KERNEL = "fused_expert_ffn_pallas"


def read(run):
    if run.trace is None or run.peaks is None:
        return None
    t = T.op_time_ns(run.trace, KERNEL, *run.trace_window) * 1e-9
    if t <= 0:
        return None
    need = sum(work.min_seconds(*work.expert_ffn(run.sizes, layer),
                                run.peaks)
               for parts in run.step_parts for p in parts
               for layer in p["slot_hist"])
    return 100.0 * need / t
