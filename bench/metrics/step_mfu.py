"""Model step: model FLOPs of the window's steps (``step_flops`` of the
configuration's architecture module) over the time they took times the
chip's bf16 peak, in %.  The time is the steps' summed synced wall time
where the engine syncs each step, and the whole window where static
batches run dispatched ahead, back to back."""


def read(run):
    if not run.steps or run.peaks is None:
        return None
    flops = sum(run.arch.step_flops(run.sizes, r.prefill, r.decode_pos)
                for r in run.steps)
    return 100.0 * flops / (run.step_seconds
                            * run.peaks["bf16_flops_per_s"])
