"""The benchmark's entry (bench/run.py) and its data: it refuses to run
without a TPU, printing no result; every name in BENCHMARK.json resolves
to its file; each metric has its reader."""
import importlib
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_entry_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", CELLS[0], "--seed", str(2 ** 31 + 3),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_every_cell_resolves():
    configs = {c["name"]: c for c in BENCH["configs"]}
    for w in BENCH["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        c = configs[w["config"]]
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and cfg["source"] == c["source"]
        assert cfg["reduced"] == c["reduced"]
        for k in cfg["reduced"]:
            assert cfg["model"][k] != cfg["published"][k]
        assert os.path.exists(os.path.join(
            ROOT, "bench", "traffic", f"{w['traffic']}.json"))


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_every_metric_has_a_reader(m):
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    mod = importlib.import_module(f"bench.metrics.{m['name']}")
    assert callable(mod.read)
    assert set(m.get("workloads", CELLS)) <= set(CELLS)
    if "moves" in m:
        moved = {e["name"]: e for e in BENCH["end_to_end"]}[m["moves"]]
        assert set(m["workloads"]) <= set(moved.get("workloads", CELLS))


def test_decode_algo_follows_the_cell_name(monkeypatch):
    """A cell named ``<config>.<traffic>.<algo>`` serves with that
    decode algorithm; the configuration's own is the default."""
    from bench import run as R
    w = BENCH["workloads"][0]
    base = w["name"].rsplit(".", 1)[0]
    extra = [dict(w, name=f"{base}.eplb"), dict(w, name=f"{base}.x")]
    bench = dict(BENCH, workloads=BENCH["workloads"] + extra)
    load = R.load_json
    monkeypatch.setattr(R, "load_json", lambda *p: bench
                        if p == ("BENCHMARK.json",) else load(*p))
    own = R.load_cell(f"{base}.x")[2]["deployment"]["decode_algo"]
    assert R.load_cell(f"{base}.eplb")[2]["deployment"][
        "decode_algo"] == "eplb"
    assert R.load_cell(f"{base}.metro")[2]["deployment"][
        "decode_algo"] == "metro"
    with open(os.path.join(ROOT, "bench", "configs",
                           f"{w['config']}.json")) as f:
        assert own == json.load(f)["deployment"]["decode_algo"]

