"""95th percentile over all requests due in the window of (finish -
first token) / (tokens - 1) (host clock), in ms."""
from bench.tails import pct


def read(run):
    v = [(t.finish - t.first) / (len(t.obj.generated) - 1)
         for t in run.requests if len(t.obj.generated) > 1]
    return pct(v, 95) * 1e3 if v else None
