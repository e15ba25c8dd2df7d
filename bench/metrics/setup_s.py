"""Seconds from process start to the window's start (host clock):
weights, engine, loading and warming every step program."""


def read(run):
    return run.setup_s
