"""Host: the share of the scoped window (``bench/scopes.py``) in which
no program runs on the device (no ``XLA Modules`` event), in %: the
idle that the host owns.  The device's idle share less this is idle
inside programs."""
from bench import scopes


def read(run):
    return scopes.window_metrics(run).get("host_gap_share")
