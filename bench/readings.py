"""Readings for the limit of ``correct``: for each seed, one window of
the cell's traffic through the engine with its known fault held off
(``bench.witness``), or one static batch through the launch steps (a
cell whose mix is ``static``; ``--seconds`` is then unused), then the
float32 reference and the fp8 control of the configuration's
architecture module (``bench/arch/``) over a sample of the finished
requests, drawn as a run draws it.

  python3 bench/readings.py --workload <cell> --seconds <s> \\
      --seeds <n> [<n> ...] [--score <k>]

Prints one JSON line per seed: the gaps of the served tokens below the
reference's best logit over each prompt and its served tokens
(``program``: the lower reading), and the gaps of the tokens the fp8
control puts first at the same positions (``control``: the upper
reading); each as max, 99th percentile and mean (the mean is the number
the benchmark compares).  ``--score k`` also scores the first ``k``
requests of the check's order and reports each one's sums, so that
other sample sizes can be read from the same runs.  One process serves
every seed, the engines sharing their step programs.  The benchmark's
runs do not run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench import arch, serve, traffic  # noqa: E402
from bench import run as R  # noqa: E402
from bench.witness import admission_one_short  # noqa: E402


def summary(gaps) -> dict:
    g = np.concatenate(gaps)
    return {"max": float(g.max()), "p99": float(np.percentile(g, 99)),
            "mean": float(g.mean()), "zero_share": float((g == 0).mean())}


def one_seed(cfg, mix, seed: int, seconds: float, score: int,
             fn_cache) -> dict:
    t = time.perf_counter()
    family = arch.of(cfg)
    s = family.sizes(cfg)
    eng = serve.build_engine(cfg, mix, seed, fn_cache=fn_cache)
    with admission_one_short():
        serve.warm_up(eng, cfg, mix)
        reqs = traffic.generate(mix, seed, seconds, s.vocab)
        tracked, live, t0, t1, _ = R.drive(eng, mix, reqs, seconds)
        R.drain(eng, mix, reqs, live, t1)
    order = R.sample_order(tracked, seed)
    served = [len(c.obj.generated) for c in order]
    n_check = R.sample_size(served, cfg["check"])
    scored = order[:max(score, n_check)]
    del eng, live
    gc.collect()
    pad = family.pad_len(serve.max_len_for(cfg, mix))
    seqs = [(c.req.prompt, np.asarray(c.obj.generated, np.int32))
            for c in scored]
    res = family.served_gaps(s, seed, seqs, pad, control=True)
    return {"seed": seed, "requests": len(tracked), "finished": len(order),
            "checked": n_check, "tokens": int(sum(served[:n_check])),
            "program": summary([r["gap"] for r in res[:n_check]]),
            "control": summary([r["control_gap"] for r in res[:n_check]]),
            "per_request": [
                {"tokens": len(r["gap"]),
                 "program_sum": float(np.sum(r["gap"])),
                 "control_sum": float(np.sum(r["control_gap"])),
                 "program_max": float(np.max(r["gap"])),
                 "control_max": float(np.max(r["control_gap"]))}
                for r in res],
            "wall_s": time.perf_counter() - t}


def one_seed_static(cfg, mix, seed: int) -> dict:
    """One static batch (``bench/batch.py``), its rows sampled as a
    run samples them, scored by the reference and the control."""
    import jax
    from bench import batch
    t = time.perf_counter()
    prog = batch.Batches(cfg, mix, seed)
    family, s = prog.arch, prog.s
    sent = [prog.send(0)]
    jax.block_until_ready(sent[0].out[-1])
    served = prog.served(sent[0])
    pick = batch.sample(sent, seed, R.check_of(cfg, mix)["requests"])
    del prog, sent
    gc.collect()
    seqs = [(batch.prompts(mix, seed, i, s.vocab)[r], served[r])
            for i, r in pick]
    res = family.served_gaps(
        s, seed, seqs, family.pad_len(mix["prompt"] + mix["output"]),
        control=True)
    return {"seed": seed, "checked": len(seqs),
            "tokens": int(sum(len(o) for _, o in seqs)),
            "program": summary([r["gap"] for r in res]),
            "control": summary([r["control_gap"] for r in res]),
            "wall_s": time.perf_counter() - t}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--score", type=int, default=0,
                    help="also score this many requests of the check's "
                    "order, each reported on its own")
    args = ap.parse_args(argv)
    _, cell, cfg, mix = R.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("readings: needs a TPU", file=sys.stderr)
        return 1
    R.use_compile_cache()
    fn_cache = {}
    for seed in args.seeds:
        got = (one_seed_static(cfg, mix, seed) if mix["loop"] == "static"
               else one_seed(cfg, mix, seed, args.seconds, args.score,
                             fn_cache))
        print(json.dumps(got), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
