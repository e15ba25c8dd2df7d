"""A configuration names its architecture by its published
``model_type`` (bench/arch): the lookup, and a family given by new
files alone, run through ``run_cell`` at ``.reduced()`` size on the CPU
(the chip check skipped, as in test_bench_faults.py)."""
import sys
import types

import pytest

from bench import arch
from bench import run as R
from bench.arch import qwen3_moe as Q
from bench.serve import StepRecord

STATIC = {"loop": "static", "batch": 4, "prompt": 24, "output": 8,
          "check": {"requests": 16, "mean_logit_gap": 0.03}}


def _qwen3():
    return R.load_json("bench", "configs", "qwen3-30b-a3b.json")


def _named(cfg, model_type):
    return dict(cfg, model=dict(cfg["model"], model_type=model_type))


@pytest.mark.parametrize("model_type", ["no_such_block", "qwen3_moe.x",
                                        "../qwen3_moe", None])
def test_unknown_model_type_is_refused(model_type):
    with pytest.raises(SystemExit) as e:
        arch.of(_named(_qwen3(), model_type))
    assert repr(model_type) in str(e.value)
    assert str(arch.known()) in str(e.value)


def test_qwen3_resolves_to_its_module():
    assert "qwen3_moe" in arch.known()
    family = arch.of(_qwen3())
    assert family is Q and family.__name__ == "bench.arch.qwen3_moe"


def _toy(calls: list, reference_seed: int = 0):
    """A module that gives a family, built over the Qwen functions, each
    call recorded in ``calls``.  ``reference_seed`` perturbs its
    reference: a nonzero one scores the served tokens against the
    reference of another seed's weights."""
    mod = types.ModuleType("bench.arch.toy_moe")

    def recorded(name, fn):
        def call(*a, **k):
            calls.append(name)
            return fn(*a, **k)
        return call
    for name in ("sizes", "reduced", "step_config", "program_params",
                 "pad_len", "logits", "step_flops", "flash_decode"):
        setattr(mod, name, recorded(name, getattr(Q, name)))

    def served_gaps(s, seed, *a, **k):
        calls.append("served_gaps")
        return Q.served_gaps(s, seed + reference_seed, *a, **k)
    mod.served_gaps = served_gaps
    return mod


@pytest.mark.parametrize("reference_seed", [0, 1],
                         ids=["sound", "perturbed_reference"])
def test_new_architecture_by_new_files_alone(monkeypatch, reference_seed):
    """A configuration whose ``model_type`` names a module the harness
    has never seen runs through ``run_cell``: its sizes, program and
    weights come from that module, and so does the reference that
    decides ``correct``."""
    calls = []
    monkeypatch.setitem(sys.modules, "bench.arch.toy_moe",
                        _toy(calls, reference_seed))
    cfg = _named(Q.reduced(_qwen3()), "toy_moe")
    bench = R.load_json("BENCHMARK.json")
    out = R.run_cell(bench, bench["workloads"][0]["name"], cfg, STATIC,
                     2 ** 31 + 17, 0.5, False)
    assert {"sizes", "step_config", "program_params", "pad_len",
            "served_gaps"} <= set(calls)
    sound = reference_seed == 0
    gap = out["checks"]["mean_logit_gap"]
    assert out["correct"] is sound
    assert (gap["value"] <= gap["limit"]) is sound
    assert out["failed"] == 0 and out["attempted"] >= 16


def test_readers_count_through_the_module():
    """``step_mfu`` counts a step's FLOPs with the run's architecture
    module."""
    calls = []
    toy = _toy(calls)
    s = Q.sizes(Q.reduced(_qwen3()))
    run = R.Run(arch=toy, sizes=s, step_seconds=2.0,
                peaks={"bf16_flops_per_s": 1e12},
                steps=[StepRecord("decode", None, None, None, [], [9, 10],
                                  [])])
    got = R.reader("step_mfu")(run)
    assert calls == ["step_flops"]
    assert got == 100.0 * Q.step_flops(s, [], [9, 10]) / 2e12
