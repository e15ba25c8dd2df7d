"""The trace reduction (bench/trace.py): on hand-built traces, and on a
small trace recorded on a TPU v5e (``data/chip_trace.xplane.pb``, made
by ``record_chip_trace.py``: three rounds of a host-only ``admit`` span
then a ``step_decode`` span around a matmul and the paged flash-decode
kernel, all inside one ``window`` span)."""
import os

import pytest

from bench import trace as T

CHIP_TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "data", "chip_trace.xplane.pb")


def _trace():
    """Device ops on one plane and nested host spans, in ns:
    window 0-100; admit 0-10; step 10-60 holding pack 10-20;
    post 60-70.  Device busy 20-30 (k.1), 35-55 (fusion.3), 80-90 (k.2)."""
    ops = {"/device:TPU:0": [("k.1", 20, 30), ("fusion.3", 35, 55),
                             ("k.2", 80, 90)]}
    spans = [("window", 0, 100), ("admit", 0, 10), ("step_decode", 10, 60),
             ("pack_inputs", 10, 20), ("postprocess", 60, 70)]
    return T.Trace(ops, spans)


def test_union_merges_and_clips():
    assert T.union([(5, 9), (0, 3), (2, 4), (8, 12)], 1, 10) == [
        (1, 4), (5, 10)]


def test_busy_and_kernel_time():
    tr = _trace()
    lo, hi = T.window(tr)
    assert (lo, hi) == (0, 100)
    assert T.busy_ns(tr, lo, hi) == 40
    assert T.op_time_ns(tr, "k", lo, hi) == 20       # k.1 + k.2
    assert T.op_time_ns(tr, "fusion", lo, hi) == 20
    assert T.op_count(tr, "k", lo, hi) == 2
    assert T.top_ops(tr, lo, hi) == [["fusion.3", 20e-9], ["k.1", 10e-9],
                                     ["k.2", 10e-9]]


def test_idle_gaps_go_to_the_innermost_span():
    tr = _trace()
    gaps = dict(T.idle_gaps(tr, 0, 100))
    # idle: 0-20 (admit 0-10, pack 10-20), 30-35 and 55-60 (step),
    # 60-80 (post 60-70, window 70-80), 90-100 (window)
    assert gaps == pytest.approx({"admit": 10e-9, "pack_inputs": 10e-9,
                                  "step_decode": 10e-9,
                                  "postprocess": 10e-9, "window": 20e-9})
    assert sum(gaps.values()) == pytest.approx(60e-9)


def test_idle_gaps_outside_every_span():
    tr = T.Trace({"/device:TPU:0": [("a", 5, 10)]}, [("window", 0, 4)])
    assert dict(T.idle_gaps(tr, 0, 20)) == pytest.approx(
        {"window": 4e-9, "none": 11e-9})


@pytest.fixture(scope="module")
def chip():
    tr = T.load(CHIP_TRACE)
    return tr, T.window(tr)


def test_chip_trace_planes_and_spans(chip):
    tr, (lo, hi) = chip
    assert list(tr.ops) == ["/device:TPU:0"]
    # the device clock's lead over the host's, corrected on load
    assert 1.0e6 < tr.shift_ns < 2.0e6
    names = [n for n, _, _ in tr.spans]
    assert names.count("window") == 1
    assert names.count("admit") == 3 and names.count("step_decode") == 3
    assert 0 < hi - lo < 10e9


def test_chip_trace_kernel_and_busy(chip):
    tr, (lo, hi) = chip
    assert T.op_count(tr, "flash_decode_paged", lo, hi) == 3
    busy = T.busy_ns(tr, lo, hi)
    kernel = T.op_time_ns(tr, "flash_decode_paged", lo, hi)
    assert 0 < kernel < busy < hi - lo
    # every device op of the window lies inside a step span
    steps = [(s, e) for n, s, e in tr.spans if n == "step_decode"]
    for _, s, e in tr.ops["/device:TPU:0"]:
        if lo <= s < hi:
            assert any(a <= s and e <= b for a, b in steps)


def test_chip_trace_idle_gaps_add_up(chip):
    tr, (lo, hi) = chip
    gaps = dict(T.idle_gaps(tr, lo, hi, n=100))
    idle = (hi - lo - T.busy_ns(tr, lo, hi)) * 1e-9
    assert sum(gaps.values()) == pytest.approx(idle, rel=1e-9)
    # the admit spans sleep 2 ms each with the device idle
    assert gaps["admit"] >= 3 * 2e-3
