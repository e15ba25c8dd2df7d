"""MoE layer: replica slots lit per layer, summed over the EP ranks
(``mean_activated`` times the EP size), mean over the window's decode
calls."""


def read(run):
    v = [p["mean_activated"] * run.ep_size for parts in run.step_parts
         for p in parts if p["decode"]]
    return sum(v) / len(v) if v else None
