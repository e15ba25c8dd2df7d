"""Find a cell's knee: the same traffic offered at several fixed rates,
each window on a fresh engine (the engines of one decode algorithm
share their step programs).

  python3 bench/sweep.py --workload <cell> --seconds <s> \\
      --seeds <n> [<n> ...] --rates <r> [<r> ...] [--algos metro eplb]

Prints one JSON line per (algorithm, seed, rate): how many of the
window's requests were waiting for a slot at half the window and at its
close, how many finished in its second half, and the tails of TTFT and
TPOT of those that finished within ``--drain`` seconds of the close.
The knee is the highest rate whose queue does not grow through the
window; a sweep stops at the first rate whose queue does.  The
benchmark's runs do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import serve, traffic  # noqa: E402
from bench import run as R  # noqa: E402
from bench.sizes import sizes  # noqa: E402
from bench.tails import pct  # noqa: E402


def waiting(tracked, admitted, t) -> int:
    """Requests due by ``t`` and not yet admitted at ``t``."""
    return sum(1 for x in tracked.values()
               if x.due <= t < admitted.get(x.rid, float("inf")))


def one_window(cfg, mix, seed, rate, seconds, drain, fn_cache) -> dict:
    eng = serve.build_engine(cfg, mix, seed, fn_cache=fn_cache)
    serve.warm_up(eng, cfg, mix)
    import jax
    print(f"seed {seed} rate {rate}: memory after warm-up "
          f"{jax.devices()[0].memory_stats()}", file=sys.stderr, flush=True)
    rec = serve.Recorder(eng, spans=False)
    reqs = traffic.generate(mix, seed, seconds, sizes(cfg).vocab,
                            rate=rate)
    tracked, live, t0, t1, late = R.drive(eng, mix, reqs, seconds)
    R.drain(eng, {"loop": "closed"}, [], live, t1, limit=drain)
    adm = rec.admitted
    done = [t for t in tracked.values() if t.finish is not None]
    ttft = [t.first - t.due for t in done]
    tpot = [(t.finish - t.first) / (len(t.obj.generated) - 1)
            for t in done if len(t.obj.generated) > 1]
    half = t0 + seconds / 2
    out = {"algo": cfg["deployment"]["decode_algo"], "seed": seed,
           "rate": rate, "requests": len(tracked),
           "waiting_at_half": waiting(tracked, adm, half),
           "waiting_at_close": waiting(tracked, adm, t1),
           "finished_2nd_half_per_s": sum(
               1 for t in done if half <= t.finish < t1) / (t1 - half),
           "finished_in_drain": len(done),
           "ttft_p50_ms": pct(ttft, 50) * 1e3 if ttft else None,
           "ttft_p95_ms": pct(ttft, 95) * 1e3 if ttft else None,
           "tpot_p95_ms": pct(tpot, 95) * 1e3 if tpot else None,
           "late_max_s": max(late) if late else 0.0}
    del eng, rec, tracked, live
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--algos", nargs="+", default=None)
    ap.add_argument("--drain", type=float, default=60.0)
    ap.add_argument("--grown", type=int, default=4,
                    help="requests waiting at the close, and more than "
                    "at half the window, that end a sweep")
    args = ap.parse_args(argv)
    _, cell, cfg, mix = R.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("sweep: needs a TPU", file=sys.stderr)
        return 1
    R.use_compile_cache()
    for algo in args.algos or [cfg["deployment"]["decode_algo"]]:
        c = dict(cfg, deployment=dict(cfg["deployment"], decode_algo=algo))
        fn_cache = {}           # the step programs close over the algo
        for seed in args.seeds:
            for rate in args.rates:
                got = one_window(c, mix, seed, rate, args.seconds,
                                 args.drain, fn_cache)
                print(json.dumps(got), flush=True)
                if got["waiting_at_close"] >= max(
                        args.grown, got["waiting_at_half"] + 1):
                    break
    return 0


if __name__ == "__main__":
    sys.exit(main())
