"""The device events of a profiler trace (``*.xplane.pb``) with their
HLO ``op_name``, read straight from the protobuf wire format.

``jax.profiler.ProfileData`` gives events their names and times but not
their metadata's stats; the ``tf_op`` stat there holds the operation's
``op_name`` path, with every ``jax.named_scope`` around it (``jit(f)/
serve_step/layers/while/body/moe/experts/pallas_call:``).  Event times
are computed as ``ProfileData`` computes them (ns, rounded down), so
the two readers agree event for event.

The messages read (``tsl/profiler/protobuf/xplane.proto``), by field
number: XSpace 1 planes; XPlane 2 name, 3 lines, 4 event_metadata (map
id -> XEventMetadata), 5 stat_metadata (map id -> XStatMetadata);
XLine 2 name, 3 timestamp_ns, 4 events; XEvent 1 metadata_id,
2 offset_ps, 3 duration_ps; XEventMetadata 1 id, 2 name, 5 stats;
XStat 1 metadata_id, 5 str_value, 7 ref_value; XStatMetadata 1 id,
2 name.
"""
from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_STAT = "tf_op"


def _varint(b: bytes, i: int) -> tuple[int, int]:
    r = shift = 0
    while True:
        x = b[i]
        i += 1
        r |= (x & 0x7F) << shift
        shift += 7
        if x < 0x80:
            return r, i


def fields(b: bytes):
    """(field number, value) of one message: an int for a varint, bytes
    for a length-delimited field or a fixed-width one."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"unsupported wire type {wire}")
        yield key >> 3, v


def _map_values(pf, number: int):
    """The values (field 2) of a map field's entries."""
    for k, entry in pf:
        if k == number:
            for kk, v in fields(entry):
                if kk == 2:
                    yield v


def _text(v) -> str:
    return bytes(v).decode(errors="replace")


def _metadata(pf) -> dict[int, tuple[str, str]]:
    """Event metadata id -> (its name, its ``tf_op`` stat without the
    trailing ``:``, or "" where it has none)."""
    stat_names = {}
    for v in _map_values(pf, 5):
        m = dict(fields(v))
        stat_names[m.get(1, 0)] = _text(m.get(2, b""))
    out = {}
    for v in _map_values(pf, 4):
        md = list(fields(v))
        mid = next((x for k, x in md if k == 1), 0)
        name = _text(next((x for k, x in md if k == 2), b""))
        op = ""
        for k, st in md:
            if k != 5:
                continue
            s = dict(fields(st))
            if stat_names.get(s.get(1)) == OP_STAT:
                op = (_text(s[5]) if 5 in s
                      else stat_names.get(s.get(7), "")).rstrip(":")
        out[mid] = (name, op)
    return out


def read(path: str) -> dict[str, dict[str, list]]:
    """Per device plane, per line name: [(event name, op_name or "",
    start_ns, end_ns)] of its events, in file order."""
    with open(path, "rb") as f:
        data = f.read()
    out = {}
    for k, plane in fields(data):
        if k != 1:
            continue
        pf = list(fields(plane))
        plane_name = _text(next((v for kk, v in pf if kk == 2), b""))
        if not DEVICE_PLANE.match(plane_name):
            continue
        meta = _metadata(pf)
        lines = out.setdefault(plane_name, {})
        for kk, line in pf:
            if kk != 3:
                continue
            lf = list(fields(line))
            lname = _text(next((v for j, v in lf if j == 2), b""))
            ts = next((v for j, v in lf if j == 3), 0)
            events = lines.setdefault(lname, [])
            for j, ev in lf:
                if j != 4:
                    continue
                e = dict(fields(ev))
                name, op = meta.get(e.get(1, 0), ("", ""))
                start = ts + e.get(2, 0) // 1000
                events.append((name, op, float(start),
                               float(start + e.get(3, 0) // 1000)))
    return out
