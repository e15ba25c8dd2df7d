"""A whole benchmark run (bench/run.py ``run_cell``) at ``.reduced()``
size on the CPU, the chip check skipped.  A sound run reads ``correct``
true; with the timed path broken underneath, each fault a served cell
can have makes it false.  (One chip runs the EP group without an
exchange between chips, so that fault does not apply to these cells.)

Two timed paths: static batches through the launch prefill and serve
steps (``bench/batch.py``, the benchmark's cell), and the engine's
scheduler and executor under open-loop chat (kept for the chat cell,
which waits for the engine's fix).  The engine as it stands re-feeds
each context's last token (PERF.md, Open question 1) and reads false
with no fault planted, so its runs are made with that fault held off by
``bench.witness``: what they test is the check, which then has a sound
run to tell the faults from."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

from bench import batch
from bench import run as R
from bench.sizes import reduced
from bench.witness import admission_one_short
from repro.serving.executor import Executor

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MIX = {"loop": "open", "rate_per_s": 30.0,
       "prompt": {"mean": 20, "sigma": 0.5, "min": 8, "max": 40},
       "output": {"mean": 8, "sigma": 0.5, "min": 4, "max": 16}}


STATIC = {"loop": "static", "batch": 4, "prompt": 24, "output": 8,
          "check": {"requests": 16, "mean_logit_gap": 0.03}}


def _cfg():
    cell = R.load_json("BENCHMARK.json")["workloads"][0]
    return reduced(R.load_json("bench", "configs", f"{cell['config']}.json"))


def _run():
    bench = R.load_json("BENCHMARK.json")
    cfg = _cfg()
    cfg["engine"] = dict(cfg["engine"], max_batch=4, prefill_chunk=16,
                         mixed_prefill_budget=16)
    # the rate keeps the 4 rows full, so that a request's slot fixes its
    # row for its whole life; every finished request is checked, so a
    # fault in the second half of the rows cannot slip past the sample
    cfg["check"] = dict(cfg["check"], requests=100, min_tokens=10 ** 6)
    with admission_one_short():
        return R.run_cell(bench, "qwen3-30b-a3b.chat.metro", cfg, MIX,
                          2 ** 31 + 11, 2.0, False)


def _run_static():
    """Every row of the batches is checked (16 of the 4-row batches a
    short window sends), so that a fault in half of the rows cannot
    slip past the sample."""
    bench = R.load_json("BENCHMARK.json")
    cell = bench["workloads"][0]["name"]
    return R.run_cell(bench, cell, _cfg(), STATIC, 2 ** 31 + 13, 0.5,
                      False)


def _broken_decode(monkeypatch, breaks):
    """Every decode step built from now on runs ``breaks`` on its
    arguments and results."""
    build = Executor.decode_fn

    def decode_fn(self, bucket):
        step = build(self, bucket)

        def broken(params, tokens, pos, slot_idx, pt, cache, routing):
            return breaks(step, params, tokens, pos, slot_idx, pt, cache,
                          routing, self.cfg.vocab_size)
        return broken
    monkeypatch.setattr(Executor, "decode_fn", decode_fn)


def _state_unchanged(step, params, tokens, pos, slot, pt, cache, routing,
                     vocab):
    nxt, _, stats = step(params, tokens, pos, slot, pt, cache, routing)
    return nxt, cache, stats


def _half_batch(step, params, tokens, pos, slot, pt, cache, routing,
                vocab):
    """The second half of the rows is left out: marked as padding, and
    what it returns is the token it was fed, not one the model chose."""
    b = slot.shape[0]
    pad = jnp.full((b - b // 2,), jnp.iinfo(jnp.int32).max, jnp.int32)
    nxt, cache, stats = step(params, tokens, pos,
                             jnp.concatenate([slot[:b // 2], pad]), pt,
                             cache, routing)
    return nxt.at[b // 2:].set(tokens[b // 2:, 0]), cache, stats


def _token_altered(step, params, tokens, pos, slot, pt, cache, routing,
                   vocab):
    """Each token is altered where the step produces it: the next id
    in the vocabulary instead of the model's choice."""
    nxt, cache, stats = step(params, tokens, pos, slot, pt, cache, routing)
    return (nxt + 1) % vocab, cache, stats


def _broken_static(monkeypatch, breaks):
    """Every serve step built from now on runs ``breaks`` on its
    arguments and results."""
    build = batch._step_programs

    def programs(sc):
        prefill, decode, cache = build(sc)

        def broken(params, tokens, pos, c, routing):
            return breaks(decode, params, tokens, pos, c, routing,
                          sc.cfg.vocab_size)
        return prefill, jax.jit(broken, donate_argnums=(3,)), cache
    monkeypatch.setattr(batch, "_step_programs", programs)
    monkeypatch.setattr(batch, "_PROGRAMS", {})


def _static_state_unchanged(step, params, tokens, pos, cache, routing,
                            vocab):
    nxt, _, stats = step(params, tokens, pos, cache, routing)
    return nxt, cache, stats


def _static_half_batch(step, params, tokens, pos, cache, routing, vocab):
    """The second half of the rows is left out: what it returns is the
    token it was fed, not one the model chose."""
    b = tokens.shape[0]
    nxt, cache, stats = step(params, tokens, pos, cache, routing)
    return nxt.at[b // 2:].set(tokens[b // 2:]), cache, stats


def _static_token_altered(step, params, tokens, pos, cache, routing,
                          vocab):
    nxt, cache, stats = step(params, tokens, pos, cache, routing)
    return (nxt + 1) % vocab, cache, stats


def test_static_sound_run_reads_correct():
    out = _run_static()
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] >= 16
    assert list(out)[-1] == "checks"
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("fault", [_static_state_unchanged,
                                   _static_half_batch,
                                   _static_token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_static_fault_reads_incorrect(monkeypatch, fault):
    _broken_static(monkeypatch, fault)
    out = _run_static()
    assert out["correct"] is False
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_sound_run_reads_correct():
    out = _run()
    assert out["correct"] is True
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] <= gap["limit"]


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_fault_reads_incorrect(monkeypatch, fault):
    _broken_decode(monkeypatch, fault)
    out = _run()
    assert out["correct"] is False
    assert list(out)[-1] == "checks"
    gap = out["checks"]["mean_logit_gap"]
    assert gap["value"] > gap["limit"]
