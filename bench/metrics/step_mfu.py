"""Model step: model FLOPs of the window's steps (bench/work.py
step_flops) over the time they took times the chip's bf16 peak, in %.
The time is the steps' summed synced wall time where the engine syncs
each step, and the whole window where static batches run dispatched
ahead, back to back."""
from bench import work


def read(run):
    if not run.steps or run.peaks is None:
        return None
    flops = sum(work.step_flops(run.sizes, r.prefill, r.decode_pos)
                for r in run.steps)
    return 100.0 * flops / (run.step_seconds
                            * run.peaks["bf16_flops_per_s"])
