"""Tokens served per second: every token of the static batches sent in
the window, over the window, which ends when the last of them is done
(host clock)."""


def read(run):
    return run.tokens / run.window_s if run.tokens else None
