"""MoE layer: the step stat ``max_activated`` (most replica slots any EP
rank lights in a layer), mean over the window's decode calls."""


def read(run):
    v = [p["max_activated"] for parts in run.step_parts for p in parts
         if p["decode"]]
    return sum(v) / len(v) if v else None
