"""One run of one benchmark cell on the chip this process holds.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \\
      --trace <0|1>

Set-up (from process start: weights drawn on the device from the seed,
the engine built, every step program loaded from the compile cache and
run once) is ``setup_s``.  Then the window: the cell's traffic is
offered for ``--seconds`` seconds, open loop (each request submitted at
its due time, and timed from it) or closed loop (``concurrency``
clients, each sending its next request when the last one finished).
Requests due in the window are followed to their end while the load
goes on; one that has not finished ``DRAIN_S`` seconds after the close
has failed.  A ``static`` mix instead runs whole batches through the
launch steps, one dispatched ahead, until the time is up and the last
one is done (``bench/batch.py``).  ``--trace 1`` records the window with the profiler and
reports the per-layer metrics instead of the end-to-end ones.

Then the answers are checked: memory's peak is read, the program is
freed, and the float32 reference of the configuration's architecture
module (``bench/arch/<model_type>.py``) scores a sample of the finished
requests drawn from the seed.  The numbers compared are printed beside
their limits as the last lines on standard error and under ``checks``,
the last key of the JSON line that ends standard output.

Without a TPU, or with fewer chips than the cell asks for, the run
exits 1 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()       # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import deque  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import numpy as np  # noqa: E402

from bench.tails import pct  # noqa: E402

DRAIN_S = 150.0          # a request due in the window that has not
                         # finished this long after the close has failed
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
DECODE_ALGOS = ("metro", "eplb")


def load_json(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(benchmark, cell, configuration, traffic mix) of a cell name.  A
    name whose last part names a decode algorithm (``<config>.<traffic>.
    eplb``) serves with it, whatever the configuration's default."""
    bench = load_json("BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; BENCHMARK.json has "
                         f"{sorted(cells)}")
    cell = cells[name]
    cfg = load_json("bench", "configs", f"{cell['config']}.json")
    mix = load_json("bench", "traffic", f"{cell['traffic']}.json")
    algo = name.rsplit(".", 1)[-1]
    if algo in DECODE_ALGOS:
        cfg = dict(cfg, deployment=dict(cfg["deployment"],
                                        decode_algo=algo))
    return bench, cell, cfg, mix


def cell_metrics(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a cell reports in a run of this kind."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def reader(name: str):
    return importlib.import_module(f"bench.metrics.{name}").read


class Tracked:
    """A request followed from its due time on the host clock."""

    def __init__(self, req, due: float, rid: int, obj):
        self.req, self.due, self.rid, self.obj = req, due, rid, obj
        self.first = self.finish = None


class Run:
    """What one run recorded; the metric readers read it.  ``arch`` is
    the configuration's architecture module (``bench/arch/``) and
    ``sizes`` the sizes it gives."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def _submit(eng, req, due_abs, tracked):
    """Submit ``req`` and follow it from its due time ``due_abs``."""
    rid = eng.submit(req.prompt, req.max_new_tokens)
    t = Tracked(req, due_abs, rid, eng.state.queue[-1])
    if tracked is not None:
        tracked[rid] = t
    return t


def _stamp(live: dict, now: float):
    for rid in list(live):
        t = live[rid]
        if t.first is None and t.obj.generated:
            t.first = now
        if t.obj.done:
            t.finish = now
            del live[rid]


def drive(eng, mix, requests, seconds: float, on_open=None):
    """Offer the traffic for ``seconds`` on the host clock, then follow
    the requests submitted in the window to their end.  Returns
    (tracked requests, window start, window end, generator lateness)."""
    import jax
    tracked: dict[int, Tracked] = {}
    live: dict[int, Tracked] = {}
    late = []
    closed_loop = mix["loop"] == "closed"
    with jax.profiler.TraceAnnotation("bench.window"):
        if on_open:
            on_open()
        t0 = time.perf_counter()
        if closed_loop:
            pool = deque(requests)
            for _ in range(mix["concurrency"]):
                req = pool.popleft()
                pool.append(req)
                t = _submit(eng, req, t0, tracked)
                live[t.rid] = t
            free = 0
        else:
            pending = deque(requests)
        while True:
            now = time.perf_counter()
            if now - t0 >= seconds:
                break
            if closed_loop:
                for _ in range(free):
                    req = pool.popleft()
                    pool.append(req)
                    t = _submit(eng, req, now, tracked)
                    live[t.rid] = t
                free = 0
            else:
                while pending and t0 + pending[0].due <= now:
                    req = pending.popleft()
                    late.append(now - (t0 + req.due))
                    t = _submit(eng, req, t0 + req.due, tracked)
                    live[t.rid] = t
            if eng.has_work:
                eng.step()
                n_live = len(live)
                _stamp(live, time.perf_counter())
                free = n_live - len(live) if closed_loop else 0
            else:
                nxt = t0 + pending[0].due if not closed_loop and pending \
                    else t0 + seconds
                time.sleep(max(0.0, min(nxt, t0 + seconds) - now))
        t1 = time.perf_counter()
    return tracked, live, t0, t1, late


def drain(eng, mix, requests, live: dict, t_close: float,
          limit: float = DRAIN_S):
    """Step until every tracked request has finished (or ``limit``
    seconds passed).  An open loop's arrivals go on meanwhile, the window's
    requests again one window later, so the last of the window's
    requests see the same load; they are not tracked."""
    background = deque(requests) if mix["loop"] == "open" else deque()
    while live and time.perf_counter() - t_close < limit:
        now = time.perf_counter()
        while background and t_close + background[0].due <= now:
            req = background.popleft()
            eng.submit(req.prompt, req.max_new_tokens)
        if eng.has_work:
            eng.step()
            _stamp(live, time.perf_counter())
        elif background:
            time.sleep(max(0.0, t_close + background[0].due - now))
        else:
            break


def sample_order(tracked, seed: int) -> list:
    """The finished requests in the order the check takes them: the
    longest (prompt plus answer), then the others in an order drawn
    from the seed."""
    done = [t for t in tracked.values() if t.finish is not None]
    if not done:
        return []
    done.sort(key=lambda t: t.rid)
    longest = max(done, key=lambda t: len(t.req.prompt)
                  + len(t.obj.generated))
    rng = np.random.default_rng(int(seed) + 1)
    return [longest] + [done[i] for i in rng.permutation(len(done))
                        if done[i] is not longest]


def sample_size(served: list[int], check: dict) -> int:
    """How many of the requests in :func:`sample_order`, serving
    ``served`` tokens each, the check scores: until ``check
    ["min_tokens"]`` served tokens or ``check["requests"]`` requests."""
    n = n_tok = 0
    for k in served:
        if n_tok >= check["min_tokens"] or n >= check["requests"]:
            break
        n, n_tok = n + 1, n_tok + k
    return n


def sample_checked(tracked, seed: int, check: dict):
    """The finished requests the reference scores."""
    order = sample_order(tracked, seed)
    return order[:sample_size([len(t.obj.generated) for t in order],
                              check)]


def check_answers(cfg, seed, seqs, pad_to: int) -> dict:
    """The gap of each served token's logit below the reference's best,
    over ``seqs`` [(prompt, served tokens)], each padded to ``pad_to``:
    its mean (the number compared) and its widest (printed).  The widest
    is set by single near-ties and reads alike for the program and for
    its fp8 control; the mean separates them (PERF.md section 6).  The
    reference is the configuration's architecture module's."""
    from bench import arch
    family = arch.of(cfg)
    res = family.served_gaps(family.sizes(cfg), seed, seqs,
                             family.pad_len(pad_to))
    gaps = np.concatenate([r["gap"] for r in res])
    return {"mean_logit_gap": float(gaps.mean()),
            "max_logit_gap": float(gaps.max()), "tokens": int(gaps.size),
            "requests": len(seqs)}


def check_of(cfg: dict, mix: dict) -> dict:
    """The check a cell's runs make: its traffic's, else its
    configuration's."""
    return mix.get("check", cfg["check"])


def measure_served(cfg, mix, seed, seconds, trace, t_start, dev):
    """The engine under open- or closed-loop traffic for ``seconds``.
    Returns (the run's record, requests attempted, requests failed, the
    sampled [(prompt, served tokens)] for the check, the length they
    are padded to); the engine is gone when it returns."""
    import jax
    from bench import arch, serve, traffic, work

    family = arch.of(cfg)
    s = family.sizes(cfg)
    eng = serve.build_engine(cfg, mix, seed)
    serve.warm_up(eng, cfg, mix)
    requests = traffic.generate(mix, seed, seconds, s.vocab)
    rec = serve.Recorder(eng, spans=trace)
    compiles = eng.slo.total_compiles
    tdir = _trace_start(trace)

    def opened():
        rec.active = True
    tracked, live, t0, t1, late = drive(eng, mix, requests, seconds,
                                        on_open=opened)
    rec.active = False
    if trace:
        jax.profiler.stop_trace()
    window_compiles = eng.slo.total_compiles - compiles
    drain(eng, mix, requests, live, t1)
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    reqs = list(tracked.values())
    failed = [t for t in reqs if t.finish is None or len(
        t.obj.generated) != t.req.max_new_tokens or min(
        t.obj.generated) < 0 or max(t.obj.generated) >= s.vocab]
    ok = [t for t in reqs if t not in failed]
    run = Run(
        arch=family, sizes=s, cfg=cfg, mix=mix, window_s=t1 - t0,
        setup_s=t0 - t_start, requests=ok, attempted=len(reqs),
        tokens=None,
        steps=rec.steps, step_parts=[serve.step_stats(r)
                                     for r in rec.steps],
        step_seconds=sum(r.wall for r in rec.steps),
        peaks=work.peaks(dev.device_kind) if dev.platform == "tpu"
        else None,
        memory_peak_bytes=peak, ep_size=cfg["deployment"]["ep_size"],
        trace_dir=tdir, trace=None, trace_window=None)
    lateness = (f"max {max(late):.6f} s, p95 {pct(late, 95):.6f} s"
                if late else "none (closed loop)")
    print(f"window: {run.window_s:.3f} s, {len(rec.steps)} steps, "
          f"window_compiles {window_compiles}, requests {len(reqs)} "
          f"({len(failed)} failed), generator lateness {lateness}",
          file=sys.stderr)
    chosen = sample_checked(tracked, seed, check_of(cfg, mix))
    seqs = [(t.req.prompt, np.asarray(t.obj.generated, np.int32))
            for t in chosen]
    return run, len(reqs), len(failed), seqs, serve.max_len_for(cfg, mix)


def measure_static(cfg, mix, seed, seconds, trace, t_start, dev):
    """Static batches through the launch steps for ``seconds``
    (``bench/batch.py``); returns as :func:`measure_served` does.  A
    batch is one request per row; a row failed where a served token
    lies outside the vocabulary."""
    import jax
    from bench import batch, serve, work

    prog = batch.Batches(cfg, mix, seed)
    batch.warm_up(prog)
    compiled = prog.compiled()
    tdir = _trace_start(trace)
    sent, t0, t1 = batch.drive(prog, seconds)
    if trace:
        jax.profiler.stop_trace()
    window_compiles = prog.compiled() - compiled
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))

    s = prog.s
    served = [prog.served(b) for b in sent]
    bad = sum(int(((o < 0) | (o >= s.vocab)).any(axis=1).sum())
              for o in served)
    rows = mix["batch"] * len(sent)
    steps = batch.steps(mix, sent)
    run = Run(
        arch=prog.arch, sizes=s, cfg=cfg, mix=mix, window_s=t1 - t0,
        setup_s=t0 - t_start, requests=[], attempted=rows,
        tokens=served[0].size * len(sent),
        steps=steps, step_parts=[serve.step_stats(r) for r in steps],
        step_seconds=t1 - t0,
        peaks=work.peaks(dev.device_kind) if dev.platform == "tpu"
        else None,
        memory_peak_bytes=peak, ep_size=cfg["deployment"]["ep_size"],
        trace_dir=tdir, trace=None, trace_window=None)
    print(f"window: {run.window_s:.3f} s, {len(sent)} batches of "
          f"{mix['batch']} x ({mix['prompt']} + {mix['output']}) tokens, "
          f"{len(steps)} steps, window_compiles {window_compiles}, "
          f"rows with a token outside the vocabulary {bad}",
          file=sys.stderr)
    pick = batch.sample(sent, seed, check_of(cfg, mix)["requests"])
    seqs = [(batch.prompts(mix, seed, i, s.vocab)[r], served[i][r])
            for i, r in pick]
    return run, rows, bad, seqs, mix["prompt"] + mix["output"]


def _trace_start(trace: bool):
    import jax
    if not trace:
        return None
    tdir = tempfile.mkdtemp(prefix="bench-trace-")
    jax.profiler.start_trace(tdir)
    return tdir


def run_cell(bench, cell, cfg, mix, seed: int, seconds: float,
             trace: bool, t_start: float = None) -> dict:
    """One run of ``cell`` (its name) on whatever backend JAX has; the
    caller checks the device.  Returns the result line's object."""
    import jax

    t_start = T_START if t_start is None else t_start
    dev = jax.devices()[0]
    measure = measure_static if mix["loop"] == "static" else measure_served
    run, attempted, failed, seqs, pad_to = measure(
        cfg, mix, seed, seconds, trace, t_start, dev)
    breakdown = None
    if trace:
        from bench import trace as T
        tr = T.load(T.find(run.trace_dir))
        run.trace = tr
        run.trace_window = T.window(tr)
        lo, hi = run.trace_window
        breakdown = {"device_ops": T.top_ops(tr, lo, hi),
                     "idle_gaps": T.idle_gaps(tr, lo, hi)}
        busy_s = T.busy_ns(tr, lo, hi) * 1e-9
        shutil.rmtree(run.trace_dir, ignore_errors=True)

    metrics = {}
    for m in cell_metrics(bench, cell, trace):
        v = reader(m["name"])(run)
        if v is None:
            print(f"metric {m['name']}: nothing to read in this run",
                  file=sys.stderr)
            continue
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    peak = run.memory_peak_bytes

    # the check: the program is freed, so the reference has the chip
    del run
    gc.collect()
    checks = {"failed_requests": {"value": failed, "limit": 0}}
    if seqs:
        got = check_answers(cfg, seed, seqs, pad_to)
        checks["mean_logit_gap"] = {
            "value": got["mean_logit_gap"],
            "limit": check_of(cfg, mix)["mean_logit_gap"]}
        print(f"check: {got['tokens']} served tokens of {got['requests']} "
              f"requests scored against the float32 reference; widest "
              f"gap {got['max_logit_gap']}", file=sys.stderr)
    correct = bool(seqs) and all(c["value"] <= c["limit"]
                                 for c in checks.values())
    for k, c in checks.items():
        print(f"{k} {c['value']} limit {c['limit']}", file=sys.stderr)

    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": len(jax.devices()),
                      "memory_peak_bytes": peak}}
    if trace:
        out["device"].update(busy_s=busy_s, window_s=(hi - lo) * 1e-9)
        out["breakdown"] = breakdown
    out["checks"] = checks
    return out


def use_compile_cache():
    """JAX's persistent compile cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one; every
    program is cached, however fast it compiled."""
    import jax
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench, cell, cfg, mix = load_cell(args.workload)
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 1
    use_compile_cache()
    out = run_cell(bench, cell["name"], cfg, mix, args.seed, args.seconds,
                   bool(args.trace))
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
