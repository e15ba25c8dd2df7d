"""The program's serving engine, seen from the benchmark: a configuration
file turned into a ``ServingEngine`` over the seeded weights, and the
instrumentation the benchmark wraps around the engine's layer calls.

The model, its ``Dist`` and its weights come from the configuration's
architecture module (``bench/arch/``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import time

import jax
import numpy as np

from bench import arch
from repro.serving import EngineConfig, ServingEngine
from repro.serving.kv import pages_for


def max_len_for(cfg: dict, traffic: dict) -> int:
    """KV capacity per sequence: the longest prompt plus the longest
    answer plus the re-fed token, rounded up to whole pages."""
    ps = cfg["engine"]["page_size"]
    need = traffic["prompt"]["max"] + traffic["output"]["max"] + 1
    return pages_for(need, ps) * ps


def engine_config(cfg: dict, traffic: dict) -> EngineConfig:
    e, dep = cfg["engine"], cfg["deployment"]
    return EngineConfig(
        max_len=max_len_for(cfg, traffic),
        replication_ratio=dep["replication_ratio"],
        decode_algo=dep["decode_algo"], prefill_algo=dep["prefill_algo"],
        **e)


def build_engine(cfg: dict, traffic: dict, seed: int,
                 fn_cache=None) -> ServingEngine:
    """The cell's engine over weights drawn from ``seed``, built as
    ``repro.launch.serve.build_engine`` builds one (EPLB's initial
    placement), with the model, its ``Dist`` and the weights handed in
    by the configuration's architecture module.  Engines of one
    configuration may share ``fn_cache`` (their step programs)."""
    family = arch.of(cfg)
    sc, placement = family.step_config(cfg)
    params = family.program_params(cfg, seed, placement)
    jax.block_until_ready(params)
    ecfg = dataclasses.replace(engine_config(cfg, traffic), seed=seed)
    return ServingEngine(sc.cfg, sc.dist, params, ecfg, fn_cache=fn_cache)


# ----------------------------------------------------------------------
# instrumentation around the engine's layer calls
# ----------------------------------------------------------------------


def _row_pos(r) -> int:
    """Position a decode row writes (the executor's rule)."""
    return r.n_ctx if r.prefilling else r.pos


@dataclasses.dataclass
class StepRecord:
    kind: str                   # "decode" | "mixed" | "chunk" | "prefill"
    t0: float                   # host clock around the executor call
    t1: float                   # (None where steps run dispatched ahead)
    wall: float                 # the executor's own synced step time
    prefill: list               # [(start, n_tokens)] per chunk row
    decode_pos: list            # position written per live decode row
    stats: list                 # the step's stats dicts (device arrays)


class Recorder:
    """Wraps an engine's executor calls to keep one :class:`StepRecord`
    per step, stamps each request's first admission on the host clock
    and, when tracing, to put host spans (admission, input
    packing, the step call, post-processing, rebalance) into the
    profiler's trace.  ``active`` gates the recording to the window."""

    SPANS = {"sched.admit": "admit", "exec.chunk_inputs": "pack_inputs",
             "exec.decode_inputs": "pack_inputs",
             "_postprocess_decode": "postprocess",
             "exec.rebalance": "rebalance"}

    def __init__(self, eng: ServingEngine, spans: bool):
        self.eng = eng
        self.steps: list[StepRecord] = []
        self.admitted: dict[int, float] = {}   # rid -> first admission
        self.active = False
        self._wrap(eng.sched, "admit", self._admit)
        ex = eng.exec
        self._wrap(ex, "run_decode", self._decode)
        self._wrap(ex, "run_mixed", self._mixed)
        self._wrap(ex, "run_chunk", self._chunk)
        if spans:
            for path, name in self.SPANS.items():
                obj = eng
                *parents, attr = path.split(".")
                for p in parents:
                    obj = getattr(obj, p)
                self._span(obj, attr, name)

    @staticmethod
    def _wrap(obj, attr, make):
        setattr(obj, attr, make(getattr(obj, attr)))

    @staticmethod
    def _span(obj, attr, name):
        inner = getattr(obj, attr)

        def spanned(*a, **k):
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                return inner(*a, **k)
        setattr(obj, attr, spanned)

    def _timed(self, kind, inner, args, prefill, drows, stats_of):
        t0 = time.perf_counter()
        with (jax.profiler.TraceAnnotation(f"bench.step_{kind}")
              if self.active else contextlib.nullcontext()):
            out = inner(*args)
        t1 = time.perf_counter()
        if self.active:
            self.steps.append(StepRecord(
                kind, t0, t1, out[-1], prefill,
                [_row_pos(r) for r in drows], stats_of(out)))
        return out

    def _admit(self, inner):
        def admit():
            got = inner()
            now = time.perf_counter()
            for r in got:
                self.admitted.setdefault(r.rid, now)
            return got
        return admit

    def _decode(self, inner):
        def run(drows, b, kvman):
            return self._timed("decode", inner, (drows, b, kvman), [],
                               drows, lambda o: [o[1]])
        return run

    def _mixed(self, inner):
        def run(pwork, drows, bp, bd, kvman):
            return self._timed(
                "mixed", inner, (pwork, drows, bp, bd, kvman),
                [(r.pos, n) for r, n in pwork], drows,
                lambda o: [o[1], o[2]])
        return run

    def _chunk(self, inner):
        def run(pwork, bp, kvman):
            return self._timed("chunk", inner, (pwork, bp, kvman),
                               [(r.pos, n) for r, n in pwork], [],
                               lambda o: [o[0]])
        return run


def step_stats(rec: StepRecord) -> list[dict]:
    """A step's stats on the host: one dict per part (the chunk part,
    then the decode part of a mixed step), each with ``decode``.  A
    static batch's ``prefill`` step is one prefill part."""
    parts = []
    decode_flags = {"decode": [True], "mixed": [False, True],
                    "chunk": [False], "prefill": [False]}[rec.kind]
    for st, dec in zip(rec.stats, decode_flags):
        parts.append({"decode": dec,
                      "max_activated": float(np.asarray(
                          st["max_activated"])),
                      "mean_activated": float(np.asarray(
                          st["mean_activated"])),
                      "slot_hist": np.asarray(st["slot_hist"])})
    return parts


def prefill_rows(cfg: dict, traffic: dict) -> list[int]:
    """The prefill row buckets a step can reach.  Rows are planned in
    order, each taking what is left of its context up to the token
    budget; every row but the last finishes its context in the step.
    So a step holds at most: one row's remainder (>= 1 token), whole
    contexts of at least the shortest prompt, and one partial row:
    ``2 + (budget - 2) // shortest`` rows, bucketed to powers of two."""
    e = cfg["engine"]
    budget = e["mixed_prefill_budget"]
    rows = min(e["max_batch"], 2 + (budget - 2) // traffic["prompt"]["min"])
    top = 1 << (rows - 1).bit_length()
    return [1 << i for i in range(top.bit_length())]


def step_shapes(cfg: dict, traffic: dict) -> dict:
    """Every step program the window can reach, as the executor keys
    them.  With a fixed decode bucket, a chunk-only step has one row (a
    row finishing its context joins the decode batch, which makes the
    step a mixed one)."""
    b = cfg["engine"]["max_batch"]
    return {"chunk": [1],
            "mixed": [(bp, b) for bp in prefill_rows(cfg, traffic)],
            "decode": [b]}


def warm_up(eng: ServingEngine, cfg: dict, traffic: dict):
    """Compile (or load from the persistent cache) and run once every
    step program of :func:`step_shapes`, and the rebalance's regather.

    A prompt two tokens longer than the budget, alone, runs a chunk
    step, then a mixed step, then a decode step (also when admission
    holds the context's last token back, as ``bench.witness`` does);
    ``bp`` prompts of
    ``budget // bp`` tokens submitted together run a mixed step of
    ``bp`` prefill rows."""
    e = eng.ecfg
    assert e.bucket_mode == "fixed" and e.mixed_prefill_budget == \
        e.prefill_chunk, "the warm-up covers a fixed decode bucket and " \
        "a one-chunk token budget"
    want = step_shapes(cfg, traffic)
    rng = np.random.default_rng(0)
    rounds = [[e.prefill_chunk + 2]] + [[e.prefill_chunk // bp] * bp
                                         for bp, _ in want["mixed"]]
    for lens in rounds:
        for n in lens:
            eng.submit(rng.integers(0, eng.cfg.vocab_size, n).astype(
                np.int32), 2)
        while eng.has_work:
            eng.step()
    eng.rebalance()
    jax.block_until_ready(eng.params)
    got = {k: sorted(eng.exec.compiled_buckets(k)) for k in want}
    assert got == {k: sorted(v) for k, v in want.items()}, \
        f"warm-up reached {got}, the window can reach {want}"
