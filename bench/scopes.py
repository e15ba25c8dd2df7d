"""Device time by layer, from the scope paths of a profiler trace.

The program names its layers with ``jax.named_scope`` (the contract is
``repro.models.scopes``; the names are kept here too, so that the
benchmark reads a program that lacks them, where it finds nothing).  A
device operation's scope path is the contract's names in its
``op_name`` (``bench/xplane.py``), outermost first:
``jit(decode)/serve_step/layers/while/body/moe/experts/pallas_call``
reads ``serve_step/layers/moe/experts``.  Operations with no
``op_name`` (the compiler's copies) have the empty path.

Reductions, over the leaf operations of the first device plane (a
loop's or a call's event spans its body's, which are listed too), each
clipped to a window:

- the device time under a scope within a step scope;
- a scope's self time: its operations under no scope of its own;
- the share of the window in which no program (``XLA Modules`` event)
  runs: idle that the host owns.  The rest of the device's idle time
  lies inside programs.

``window_metrics`` records the trace it reads, after the measured
window: the prefill and first ``DECODE_STEPS`` decode steps of one
static batch of the cell, on weights and prompts from ``SEED``, in a
traced window of its own.  The harness's own trace is gone by the time
the metric readers run, and its reduction keeps no scope paths.  A
short window also keeps every event: the profiler drops events past a
few million, and the measured window's 37 s trace of the static cell
lost a sixth of its programs on a TPU v5e.
"""
from __future__ import annotations

import bisect
import dataclasses
import json
import shutil
import sys
import tempfile

from bench import trace as T
from bench import xplane

STEPS = ("prefill_step", "serve_step", "decode_step", "chunk_step",
         "mixed_step", "wave_step")
LAYERS = ("embed", "layers", "attention", "mamba", "mlp", "moe", "head",
          "router", "route", "dispatch", "experts", "combine",
          "shared_expert", "collective", "kv_write")
CONTRACT = frozenset(STEPS + LAYERS)
UNSCOPED = "(unscoped)"
SEED = 0                  # weights and prompts of the scoped window
DECODE_STEPS = 64         # decode steps it traces
SPAN = "scoped"           # its host span (``bench.scoped``)
DECODE = "serve_step"     # the static path's decode step
ROUTING = ("router", "route", "dispatch", "combine")
METRICS = ("decode_attention_ms", "decode_routing_ms", "decode_experts_ms",
           "decode_carry_ms", "host_gap_share")


def scope_path(op_name: str) -> str:
    """The contract's scopes in an ``op_name``, outermost first; its
    last part names the operation, never a scope."""
    return "/".join(p for p in op_name.split("/")[:-1] if p in CONTRACT)


@dataclasses.dataclass
class Scoped:
    ops: list        # [(scope path, start_ns, end_ns, op name)], leaves
    modules: list    # [(start_ns, end_ns)] program executions
    spans: list      # host spans, as ``bench.trace.Trace.spans``


def load(path: str) -> Scoped:
    """The first device plane's leaf operations with their scope paths
    and its program executions, on the host spans' clock (the shift
    ``bench.trace.load`` finds)."""
    tr = T.load(path)
    planes = xplane.read(path)
    lines = planes[sorted(planes)[0]] if planes else {}
    sh = tr.shift_ns
    ops = [(scope_path(op), s + sh, e + sh, T.op_name(name))
           for name, op, s, e in lines.get(T.OPS_LINE, [])]
    leaves = T.leaves([(i, s, e) for i, (_, s, e, _) in enumerate(ops)])
    return Scoped([ops[i] for i, _, _ in leaves],
                  sorted((s + sh, e + sh) for _, _, s, e in
                         lines.get(T.MODULES_LINE, [])),
                  tr.spans)


def _clip(s: float, e: float, lo: float, hi: float) -> float:
    return max(0.0, min(e, hi) - max(s, lo))


def path_times(sc: Scoped, lo: float, hi: float) -> dict[str, float]:
    """Device time in [lo, hi] by scope path (ns)."""
    acc: dict[str, float] = {}
    for p, s, e, _ in sc.ops:
        if e > lo and s < hi:
            acc[p] = acc.get(p, 0.0) + _clip(s, e, lo, hi)
    return acc


def _within(path: str, step: str) -> list[str]:
    """The scopes of ``path`` below ``step`` ([] where it lies outside
    ``step``)."""
    parts = path.split("/")
    return parts[parts.index(step) + 1:] if step in parts else []


def scope_ns(times: dict, step: str, *scopes: str) -> float:
    """Device time under any of ``scopes`` within ``step``; ``times``
    from :func:`path_times`."""
    return sum(t for p, t in times.items()
               if any(x in _within(p, step) for x in scopes))


def self_ns(times: dict, step: str, scope: str) -> float:
    """Device time of the operations of ``scope`` within ``step`` that
    lie under none of its child scopes."""
    return sum(t for p, t in times.items()
               if scope in _within(p, step) and p.split("/")[-1] == scope)


def program_share(sc: Scoped, lo: float, hi: float) -> float:
    """Share of [lo, hi] in which some program runs on the device."""
    run = sum(e - s for s, e in T.union(sc.modules, lo, hi))
    return run / (hi - lo)


def step_programs(sc: Scoped, step: str) -> list[tuple[float, float]]:
    """The program executions that run operations of ``step``."""
    starts = sorted(s for p, s, _, _ in sc.ops if step in p.split("/"))
    out, i = [], 0
    for s, e in sc.modules:
        while i < len(starts) and starts[i] < s:
            i += 1
        if i < len(starts) and starts[i] < e:
            out.append((s, e))
    return out


def coverage(sc: Scoped, step: str, lo: float, hi: float) -> float:
    """Share of the busy time of ``step``'s programs, in [lo, hi], that
    lies under a layer scope (one below the step's)."""
    progs = T.union(step_programs(sc, step), lo, hi)
    starts = [a for a, _ in progs]
    total = scoped = 0.0
    for p, s, e, _ in sc.ops:
        i = bisect.bisect_right(starts, s) - 1    # the program it starts in
        t = _clip(s, e, *progs[i]) if i >= 0 else 0.0
        total += t
        parts = p.split("/")
        if step in parts and parts[-1] != step:
            scoped += t
    return scoped / total if total else 0.0


def top_scopes(times: dict, n: int = 12):
    """[(scope path, seconds)] with the most device time; ``times`` from
    :func:`path_times`, operations with no scope as ``(unscoped)``."""
    return [[k or UNSCOPED, v * 1e-9] for k, v in
            sorted(times.items(), key=lambda kv: -kv[1])[:n]]


def top_ops(sc: Scoped, lo: float, hi: float, n: int = 10):
    """[(op name, scope path, seconds)] of the operations with the most
    device time in [lo, hi], the scope of each."""
    acc: dict[tuple[str, str], float] = {}
    for p, s, e, name in sc.ops:
        acc[name, p] = acc.get((name, p), 0.0) + _clip(s, e, lo, hi)
    return [[k[0], k[1] or UNSCOPED, v * 1e-9] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def decode_metrics(sc: Scoped, lo: float, hi: float) -> dict:
    """The per-layer metrics of a window: device time by scope per
    decode step (each program execution that runs ``serve_step``
    operations is one), in ms, and ``host_gap_share`` in %.  A scope the
    program does not have reads None, and every metric does where the
    trace holds no device."""
    if not sc.modules:
        return dict.fromkeys(METRICS)
    times = path_times(sc, lo, hi)
    steps = sum(1 for s, e in step_programs(sc, DECODE) if e > lo and s < hi)

    def ms(t):
        return t * 1e-6 / steps if t > 0 and steps else None

    carry = None
    if ms(scope_ns(times, DECODE, "layers")) is not None:
        carry = self_ns(times, DECODE, "layers") * 1e-6 / steps
    return {"decode_attention_ms": ms(scope_ns(times, DECODE, "attention")),
            "decode_routing_ms": ms(scope_ns(times, DECODE, *ROUTING)),
            "decode_experts_ms": ms(scope_ns(times, DECODE, "experts")),
            "decode_carry_ms": carry,
            "host_gap_share": 100.0 * (1.0 - program_share(sc, lo, hi))}


def record(cfg: dict, mix: dict) -> tuple[Scoped, tuple]:
    """Trace the start of one static batch of the cell on a model of its
    own (weights and prompts from ``SEED``): its prefill and its first
    ``DECODE_STEPS`` decode steps, dispatched as the measured window
    dispatches a batch, inside the host span ``bench.scoped``.  Every
    program is already compiled in this process.  Returns (the reduced
    trace, its window)."""
    import jax
    from bench import batch
    prog = batch.Batches(cfg, mix, SEED)
    steps = 1 + min(DECODE_STEPS, mix["output"] - 1)
    tdir = tempfile.mkdtemp(prefix="bench-scopes-")
    try:
        jax.profiler.start_trace(tdir)
        with jax.profiler.TraceAnnotation(T.SPAN_PREFIX + SPAN):
            with jax.profiler.TraceAnnotation(T.SPAN_PREFIX + "dispatch"):
                sent = prog.send(0, steps=steps)
            with jax.profiler.TraceAnnotation(T.SPAN_PREFIX + "wait"):
                jax.block_until_ready(sent.out[-1])
        jax.profiler.stop_trace()
        del prog, sent
        sc = load(T.find(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    win = [(s, e) for n, s, e in sc.spans if n == SPAN]
    assert len(win) == 1, f"{len(win)} '{SPAN}' spans in the trace"
    return sc, win[0]


def window_metrics(run) -> dict:
    """The scoped window's metrics for a traced run of a static cell
    (recorded once per run and kept on it), else {}.  Prints the
    window's busy and program shares, the layer scopes' coverage of the
    decode programs' busy time, and the top scopes and operations."""
    if run.trace is None or run.mix.get("loop") != "static":
        return {}
    if getattr(run, "scoped_metrics", None) is None:
        sc, (lo, hi) = record(run.cfg, run.mix)
        run.scoped_metrics = decode_metrics(sc, lo, hi)
        if not sc.modules:
            print("scoped window: no device program in the trace",
                  file=sys.stderr)
            return run.scoped_metrics
        busy = sum(e - s for s, e in T.union(
            [(s, e) for _, s, e, _ in sc.ops], lo, hi)) / (hi - lo)
        print(f"scoped window: {(hi - lo) * 1e-9:.3f} s, {len(sc.modules)}"
              f" programs, {len(step_programs(sc, DECODE))} of them decode"
              f" steps (seed {SEED}); leaf operations busy "
              f"{100 * busy:.2f}%, a program running "
              f"{100 * program_share(sc, lo, hi):.2f}%; layer scopes cover "
              f"{100 * coverage(sc, DECODE, lo, hi):.2f}% of the decode "
              f"programs' busy time", file=sys.stderr)
        print("device_scopes: "
              + json.dumps(top_scopes(path_times(sc, lo, hi))),
              file=sys.stderr)
        print("device_ops_scoped: " + json.dumps(top_ops(sc, lo, hi)),
              file=sys.stderr)
    return run.scoped_metrics
