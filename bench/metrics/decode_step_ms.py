"""Executor: mean synced wall time of the window's pure decode steps
(the executor's own step timing), in ms."""


def read(run):
    w = [r.wall for r in run.steps if r.kind == "decode"]
    return sum(w) / len(w) * 1e3 if w else None
