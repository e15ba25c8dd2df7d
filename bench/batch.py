"""Static batches through the program's launch steps: the prefill step
and the serve (decode) step of ``repro.launch.steps``, the pair that
``launch/dryrun.py`` lowers for a pod, here on one chip over a virtual
EP group.

A batch is ``batch`` prompts of ``prompt`` tokens each, from a mix file
with ``"loop": "static"``.  The prefill step writes the prompts into a
contiguous KV cache and returns each row's first token (the argmax at
its last prompt position); each serve step feeds the tokens it returned
last at the next position and returns the next ones, ``output - 1``
times.  Tokens never leave the device between steps.

The window dispatches one batch ahead: after sending batch ``i + 1`` it
waits for batch ``i``.  When the time is up it sends nothing more,
waits for all that was sent, and reads the clock after that wait: every
token of every batch sent counts, over all that time.
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import arch, serve


@dataclasses.dataclass
class Batch:
    index: int
    out: list               # per step, the [batch] tokens it returned
    stats: list             # per step, its stats dict (device arrays)


def prompts(mix: dict, seed: int, index: int, vocab: int) -> np.ndarray:
    """Batch ``index``'s prompts [batch, prompt], a function of the seed
    and the index alone: every seed's batches have the same shapes."""
    rng = np.random.default_rng([int(seed), int(index)])
    return rng.integers(0, vocab, (mix["batch"], mix["prompt"])).astype(
        np.int32)


_PROGRAMS: dict = {}


def step_programs(sc):
    """(prefill, serve, new cache) as jitted functions: the program's
    step functions, jitted as ``launch/dryrun.py`` jits them (the serve
    step donates its cache).  The serve step takes and returns the
    tokens as [batch].  One set per step configuration and process, so
    that models of one configuration share their programs."""
    key = repr(sc)
    if key not in _PROGRAMS:
        _PROGRAMS[key] = _step_programs(sc)
    return _PROGRAMS[key]


def _step_programs(sc):
    from repro.launch.steps import make_prefill_step, make_serve_step
    from repro.models import lm as LM
    prefill_step = make_prefill_step(sc)
    serve_step = make_serve_step(sc)

    def prefill(params, tokens, cache, routing):
        return prefill_step(params, {"tokens": tokens}, cache, routing)

    def decode(params, tokens, pos, cache, routing):
        return serve_step(params, tokens[:, None], pos, cache, routing)

    def new_cache(batch, max_len):
        return LM.init_cache(sc.cfg, sc.dist, batch, max_len,
                             dtype=jnp.dtype(sc.kv_dtype))

    return (jax.jit(prefill), jax.jit(decode, donate_argnums=(3,)),
            jax.jit(new_cache, static_argnums=(0, 1)))


class Batches:
    """The cell's model over weights drawn from the seed, with its two
    step programs, run one static batch at a time.  The model, its step
    configuration and its weights come from the configuration's
    architecture module (``arch``)."""

    def __init__(self, cfg: dict, mix: dict, seed: int):
        from repro.models import lm as LM
        self.cfg, self.mix, self.seed = cfg, mix, seed
        self.arch = arch.of(cfg)
        self.s = self.arch.sizes(cfg)
        sc, placement = self.arch.step_config(cfg)
        self.params = self.arch.program_params(cfg, seed, placement)
        self.routing = LM.build_lm_routing(sc.cfg, placement)
        self.prefill, self.decode, self._cache = step_programs(sc)
        b, n = mix["batch"], mix["prompt"]
        self.pos = [jnp.full((b,), n + t, jnp.int32)
                    for t in range(mix["output"] - 1)]
        jax.block_until_ready((self.params, self.routing, self.pos))

    def send(self, index: int, tokens=None, steps=None) -> Batch:
        """Dispatch batch ``index`` (its prompts from the seed, or
        ``tokens``; its first ``steps`` steps, or all) and return
        without waiting."""
        mix = self.mix
        if tokens is None:
            tokens = prompts(mix, self.seed, index, self.s.vocab)
        cache = self._cache(mix["batch"], mix["prompt"] + mix["output"])
        tok, cache, st = self.prefill(self.params, jnp.asarray(tokens),
                                      cache, self.routing)
        out, stats = [tok], [st]
        for pos in self.pos[:None if steps is None else steps - 1]:
            tok, cache, st = self.decode(self.params, tok, pos, cache,
                                         self.routing)
            out.append(tok)
            stats.append(st)
        return Batch(index, out, stats)

    def compiled(self) -> int:
        """How many programs the three jitted functions hold (a count
        that grows when a call compiles)."""
        return sum(f._cache_size() for f in (self.prefill, self.decode,
                                             self._cache))

    def served(self, b: Batch) -> np.ndarray:
        """[batch, output] tokens a batch served."""
        return np.stack([np.asarray(t) for t in b.out], axis=1)


def warm_up(run: Batches):
    """Compile (or load from the persistent cache) and run once both
    step programs and the cache's, at the cell's shapes: a batch's
    prefill and first serve step."""
    mix = run.mix
    tokens = np.random.default_rng(0).integers(
        0, run.s.vocab, (mix["batch"], mix["prompt"])).astype(np.int32)
    jax.block_until_ready(run.send(-1, tokens, steps=2).out[-1])


def drive(run: Batches, seconds: float, on_open=None):
    """Batches for ``seconds`` on the host clock, one dispatched ahead.
    Returns (batches, window start, window end)."""
    sent: list[Batch] = []
    with jax.profiler.TraceAnnotation("bench.window"):
        if on_open:
            on_open()
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                sent.append(run.send(len(sent)))
            if len(sent) > 1:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(sent[-2].out[-1])
        with jax.profiler.TraceAnnotation("bench.wait"):
            jax.block_until_ready(sent[-1].out[-1])
        t1 = time.perf_counter()
    return sent, t0, t1


def steps(mix: dict, batches: list) -> list:
    """One ``serve.StepRecord`` per step of ``batches``: what each step
    computed, for the per-layer readers (no per-step time: the steps
    run back to back, dispatched ahead)."""
    b, n = mix["batch"], mix["prompt"]
    out = []
    for bt in batches:
        out.append(serve.StepRecord("prefill", None, None, None,
                                    [(0, n)] * b, [], [bt.stats[0]]))
        for t, st in enumerate(bt.stats[1:]):
            out.append(serve.StepRecord("decode", None, None, None, [],
                                        [n + t] * b, [st]))
    return out


def sample(batches: list, seed: int, k: int) -> list[tuple[int, int]]:
    """``k`` (batch, row) pairs of the finished batches, drawn from the
    seed.  Every sequence is equally long, so any is the longest."""
    rows = len(batches[0].out[0])
    pairs = [(i, r) for i in range(len(batches)) for r in range(rows)]
    rng = np.random.default_rng(int(seed) + 1)
    pick = rng.permutation(len(pairs))[:k]
    return [pairs[i] for i in sorted(pick)]
