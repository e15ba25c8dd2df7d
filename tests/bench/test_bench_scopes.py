"""The wire reader (bench/xplane.py) and the reduction by scope
(bench/scopes.py): on hand-built traces, on the parent's recorded chip
trace (``data/chip_trace.xplane.pb``, made by ``record_chip_trace.py``)
and on a scoped one (``data/scoped_trace.xplane.pb``, made by
``record_scoped_trace.py``: three rounds of a host-only ``admit`` span,
2 ms asleep, then a ``step_decode`` span around a ``serve_step``
program that scans two layers with ``attention``, ``moe/router`` and
``moe/experts`` scopes, the last a Pallas kernel, all inside one
``window`` span)."""
import os

import pytest
from jax.profiler import ProfileData

from bench import scopes as S
from bench import trace as T
from bench import xplane
from repro.models import scopes as contract

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CHIP_TRACE = os.path.join(DATA, "chip_trace.xplane.pb")
SCOPED_TRACE = os.path.join(DATA, "scoped_trace.xplane.pb")
PLANE = "/device:TPU:0"


def test_contract_names_are_the_programs():
    names = {v for k, v in vars(contract).items() if k.isupper()}
    assert names == S.CONTRACT


@pytest.mark.parametrize("op_name,path", [
    ("jit(decode)/serve_step/layers/while/body/closed_call/moe/experts/"
     "jit(fused_expert_ffn_pallas)/pallas_call", "serve_step/layers/moe/"
     "experts"),
    ("jit(decode)/serve_step/layers/while/body/dynamic_slice",
     "serve_step/layers"),
    ("jit(f)/serve_step/head/reduce_sum", "serve_step/head"),
    # the operation's own name is never a scope, nor is a path's text
    # that only looks like one
    ("jit(f)/serve_step/moe/router/route", "serve_step/moe/router"),
    ("jit(route_metro)/while/body/add", ""),
    ("", ""),
])
def test_scope_path(op_name, path):
    assert S.scope_path(op_name) == path


# -------------------------------------------------------------- wire reader

@pytest.mark.parametrize("path", [CHIP_TRACE, SCOPED_TRACE])
def test_wire_reader_agrees_with_profile_data(path):
    """Every device event, in order, with ProfileData's name and times."""
    got = xplane.read(path)
    pd = ProfileData.from_file(path)
    planes = [p for p in pd.planes if xplane.DEVICE_PLANE.match(p.name)]
    assert sorted(got) == [p.name for p in planes]
    n = 0
    for p in planes:
        for line in p.lines:
            want = [(e.name, e.start_ns, e.end_ns) for e in line.events]
            have = [(name, s, e) for name, _, s, e in got[p.name][line.name]]
            assert have == want
            n += len(want)
    assert n > 0


def test_wire_reader_op_names_on_the_chip_trace():
    ops = {T.op_name(name): op
           for name, op, _, _ in xplane.read(CHIP_TRACE)[PLANE][T.OPS_LINE]}
    assert ops["flash_decode_paged.1"] == (
        "jit(flash_decode_paged)/jit(flash_decode_paged)/pallas_call")
    assert ops["fusion"] == "jit(<lambda>)/dot_general"
    assert ops["copy-start"] == ""            # the compiler's own copy


def test_wire_reader_op_names_on_the_scoped_trace():
    paths = {S.scope_path(op) for _, op, _, _ in
             xplane.read(SCOPED_TRACE)[PLANE][T.OPS_LINE]}
    assert {"serve_step/layers/attention",
            "serve_step/layers/moe/experts",
            "serve_step/head"} <= paths


# ------------------------------------- the parent's reduction, unchanged

def _rows(rows):
    """[(name, seconds)] -> [(name, whole ns)]."""
    return [(r[0], round(r[-1] * 1e9)) for r in rows]


@pytest.fixture(scope="module")
def chip():
    tr = T.load(CHIP_TRACE)
    return tr, T.window(tr)


def test_parent_reduction_reads_as_before(chip):
    """The trace reduction the accepted metrics read gives, on the
    recorded trace, exactly the numbers it gave before scopes were
    read."""
    tr, (lo, hi) = chip
    assert (lo, hi, tr.shift_ns) == (49195389.0, 62728348.0, 1467341.0)
    assert T.busy_ns(tr, lo, hi) == 594271.0
    assert T.op_time_ns(tr, "flash_decode_paged", lo, hi) == 272802.0
    assert T.op_time_ns(tr, "fusion", lo, hi) == 272526.0
    assert _rows(T.top_ops(tr, lo, hi)[:5]) == [
        ("flash_decode_paged.1", 272802), ("fusion", 272526),
        ("copy-done", 35555), ("reshape.0", 8450), ("reshape.1", 4808)]
    assert _rows(T.idle_gaps(tr, lo, hi)) == [
        ("admit", 7787021), ("step_decode", 5057989), ("window", 93678)]


def test_scoped_load_keeps_the_parent_ops(chip):
    """The scoped reader's leaves are the parent reduction's, on the same
    clock: the two sum to the same device time."""
    tr, (lo, hi) = chip
    sc = S.load(CHIP_TRACE)
    times = S.path_times(sc, lo, hi)
    assert sum(times.values()) == pytest.approx(
        1e9 * sum(v for _, v in T.top_ops(tr, lo, hi, n=100)), rel=1e-12)
    assert len(sc.modules) == 6
    assert S.top_scopes(times)[0][0] == S.UNSCOPED


# ------------------------------------------------- reductions, by hand

def _scoped():
    """Two decode programs (0-40, 50-90) and one prefill (95-100), in
    ns; the window is 0-100."""
    ops = [("serve_step/embed", 0, 2, "a"),
           ("serve_step/layers", 2, 6, "slice"),          # carry
           ("serve_step/layers/attention", 6, 10, "b"),
           ("serve_step/layers/attention/kv_write", 10, 12, "c"),
           ("serve_step/layers/moe/router", 12, 14, "d"),
           ("serve_step/layers/moe/route", 14, 15, "e"),
           ("serve_step/layers/moe/dispatch", 15, 16, "f"),
           ("serve_step/layers/moe/experts", 16, 30, "k"),
           ("serve_step/layers/moe/combine", 30, 31, "g"),
           ("serve_step/layers/moe", 31, 32, "h"),
           ("", 32, 38, "copy"),
           ("serve_step/head", 38, 40, "i"),
           ("serve_step/layers/moe/experts", 50, 80, "k"),
           ("serve_step/layers", 80, 90, "slice"),
           ("prefill_step/layers/attention", 95, 100, "p")]
    modules = [(0, 40), (50, 90), (95, 100)]
    return S.Scoped(ops, modules, [("scoped", 0, 100)])


def test_scope_and_self_time():
    times = S.path_times(_scoped(), 0, 100)
    assert S.scope_ns(times, "serve_step", "attention") == 6
    assert S.scope_ns(times, "serve_step", "experts") == 44
    assert S.scope_ns(times, "prefill_step", "attention") == 5
    assert S.scope_ns(times, "serve_step", "layers") == 70
    assert S.self_ns(times, "serve_step", "layers") == 14
    assert S.self_ns(times, "serve_step", "moe") == 1
    # an operation under two of the scopes asked for counts once
    assert S.scope_ns(times, "serve_step", "moe", "router") == 50
    assert S.scope_ns(S.path_times(_scoped(), 20, 60), "serve_step",
                      "experts") == 20


def test_programs_coverage_and_host_gap():
    sc = _scoped()
    assert S.step_programs(sc, "serve_step") == [(0, 40), (50, 90)]
    assert S.program_share(sc, 0, 100) == pytest.approx(0.85)
    # of the decode programs' 80 busy ns only the copy's 6 lie under no
    # layer scope
    assert S.coverage(sc, "serve_step", 0, 100) == pytest.approx(74 / 80)


def test_decode_metrics():
    m = S.decode_metrics(_scoped(), 0, 100)
    assert m == pytest.approx({
        "decode_attention_ms": 6e-6 / 2, "decode_routing_ms": 5e-6 / 2,
        "decode_experts_ms": 44e-6 / 2, "decode_carry_ms": 14e-6 / 2,
        "host_gap_share": 15.0})


def test_decode_metrics_of_a_program_without_scopes():
    """The parent's program: nothing to read but the host's gaps."""
    sc = _scoped()
    sc.ops = [("", s, e, n) for _, s, e, n in sc.ops]
    m = S.decode_metrics(sc, 0, 100)
    assert m == {"decode_attention_ms": None, "decode_routing_ms": None,
                 "decode_experts_ms": None, "decode_carry_ms": None,
                 "host_gap_share": pytest.approx(15.0)}
    sc.modules = []                                  # no device at all
    assert set(S.decode_metrics(sc, 0, 100).values()) == {None}


def test_top_scopes_and_ops():
    sc = _scoped()
    assert _rows(S.top_scopes(S.path_times(sc, 0, 100), n=3)) == [
        ("serve_step/layers/moe/experts", 44), ("serve_step/layers", 14),
        (S.UNSCOPED, 6)]
    assert S.top_ops(sc, 0, 100, n=1)[0][:2] == [
        "k", "serve_step/layers/moe/experts"]


# --------------------------------------------- the recorded scoped trace

@pytest.fixture(scope="module")
def scoped():
    sc = S.load(SCOPED_TRACE)
    return sc, T.window(T.load(SCOPED_TRACE))


def test_scoped_trace_layers(scoped):
    sc, (lo, hi) = scoped
    step = "serve_step"
    times = S.path_times(sc, lo, hi)
    kernel = sum(e - s for p, s, e, name in sc.ops
                 if name.startswith("experts") and lo <= s < hi)
    assert S.scope_ns(times, step, "experts") == kernel > 0
    for scope in ("attention", "router", "head"):
        assert S.scope_ns(times, step, scope) > 0
    # the layers' scope holds its children and its own scan slicing
    assert S.scope_ns(times, step, "layers") == pytest.approx(
        S.scope_ns(times, step, "attention", "moe")
        + S.self_ns(times, step, "layers"))
    assert S.self_ns(times, step, "layers") > 0
    assert S.coverage(sc, step, lo, hi) > 0.5


def test_scoped_trace_programs_and_host_gap(scoped):
    sc, (lo, hi) = scoped
    assert len(S.step_programs(sc, "serve_step")) == 3      # the rounds
    busy = sum(e - s for s, e in T.union(
        [(s, e) for _, s, e, _ in sc.ops], lo, hi)) / (hi - lo)
    running = S.program_share(sc, lo, hi)
    # the device's operations run inside programs
    assert 0 < busy <= running < 1
    m = S.decode_metrics(sc, lo, hi)
    # the admit spans sleep 2 ms each with no program running
    assert m["host_gap_share"] >= 100 * 3 * 2e-3 * 1e9 / (hi - lo)
    assert m["host_gap_share"] == pytest.approx(100 * (1 - running))
    # per decode step: each of the three programs is one
    times = S.path_times(sc, lo, hi)
    assert m["decode_experts_ms"] == pytest.approx(
        S.scope_ns(times, "serve_step", "experts") * 1e-6 / 3)
