"""Reduction of a JAX profiler trace by the program's own names, beside
``trace.reduce`` (which it leaves as it is).

- ``program_idle_gaps``: the device's idle time inside the window, split
  by the innermost ``cpsl.<name>`` span (``repro.telemetry.span``) open on
  the host at that moment (``outside spans`` where none was).
- ``device_scopes``: device-busy seconds per ``jax.named_scope`` scope of
  ``core/cpsl.py`` (``SCOPES``, or ``unscoped``). Each busy moment is
  charged once, to the innermost op covering it, so a ``while`` and the
  ops of its body are not counted twice; the values sum to ``busy_s``.
- ``device_ops``: each device plane's op events with their ``tf_op``
  path, read from the ``.xplane.pb`` itself: ``jax.profiler.ProfileData``
  does not expose the stats of an event's metadata, where the path is
  kept. The file is an ``XSpace`` protobuf; its few fields read here are
  decoded by hand, so nothing outside JAX is needed.

Device events and the window are taken as ``trace.reduce`` takes them:
the ``XLA Ops`` line of each ``/device:`` plane where it has one, and the
host span ``bench.window`` (else the extent of all events).
"""
from __future__ import annotations

import re
from collections import defaultdict

from bench import trace

PREFIX = "cpsl."
SCOPES = ("device_side", "server_side", "update", "fedavg")
UNSCOPED = "unscoped"


def window(events):
    """(lo, hi) of the traced window, by ``trace.reduce``'s rule."""
    win = [(s, e) for n, s, e in trace.host_spans(events) if n == "window"]
    if win:
        return win[0]
    return (min(s for *_, s, _ in events), max(e for *_, e in events))


def program_spans(events):
    return [(name[len(PREFIX):], s, e)
            for plane, line, name, s, e in events
            if not plane.startswith("/device:") and name.startswith(PREFIX)]


def program_idle_gaps(events) -> dict:
    """{innermost open ``cpsl.*`` span: device idle seconds} in the window,
    averaged over the devices."""
    lo, hi = window(events)
    labels = trace._labels(program_spans(events), lo, hi)
    devs = trace.device_events(events)
    idle = defaultdict(float)
    for evs in devs.values():
        busy = trace._union(trace._clip([(s, e) for _, s, e in evs], lo, hi))
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        for lab, ov in trace._overlaps(labels, gaps):
            idle[lab] += ov / len(devs)
    return dict(idle)


_WORD = re.compile(r"[A-Za-z_][A-Za-z0-9_]*")


def scope_of(tf_op: str) -> str:
    """The innermost of ``SCOPES`` among the scopes of an op's path, its
    last part (the op itself) left out:
    ``jit(_fused_step)/transpose(jvp(server_side))/dot_general`` ->
    ``server_side``; ``unscoped`` where none is named."""
    scopes = (tf_op or "").split("/")[:-1]
    found = [w for part in scopes for w in _WORD.findall(part)
             if w in SCOPES]
    return found[-1] if found else UNSCOPED


def device_scopes(ops, lo: float, hi: float) -> dict:
    """{scope: device-busy seconds} in [lo, hi], averaged over the
    devices. ``ops``: {device plane: [(tf_op, start, end)]}. Where ops
    overlap, the moment goes to the innermost: the one that started last
    (of two that started together, the one that ends first)."""
    out = defaultdict(float)
    for evs in ops.values():
        iv = [(max(s, lo), min(e, hi), scope_of(op)) for op, s, e in evs
              if e > lo and s < hi and e > s]
        pts = sorted([(s, 1, i) for i, (s, _, _) in enumerate(iv)]
                     + [(e, 0, i) for i, (_, e, _) in enumerate(iv)])
        active, t = set(), lo
        for when, opening, i in pts:
            if active and when > t:
                inner = max(active, key=lambda j: (iv[j][0], -iv[j][1], j))
                out[iv[inner][2]] += (when - t) / len(ops)
            t = when
            if opening:
                active.add(i)
            else:
                active.discard(i)
    return dict(out)


# -- the XSpace protobuf, read by hand ----------------------------------------

def _varint(b: bytes, i: int):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b: bytes, lo: int, hi: int):
    """Yield (field number, value) of the message in b[lo:hi]: an int for
    a varint, a (start, end) span for a length-delimited field."""
    i = lo
    while i < hi:
        key, i = _varint(b, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
            yield num, v
        elif wire == 2:
            n, i = _varint(b, i)
            yield num, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"unexpected protobuf wire type {wire}")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _text(b: bytes, span) -> str:
    return b[span[0]:span[1]].decode("utf-8", "replace")


def _plane_ops(b: bytes, lo: int, hi: int):
    """(plane name, [(tf_op, start_s, end_s)]) of one ``XPlane``; no ops
    unless it is a device plane."""
    name, parts = "", defaultdict(list)
    for num, v in _fields(b, lo, hi):
        if num == 2:
            name = _text(b, v)
        else:
            parts[num].append(v)
    if not name.startswith("/device:"):
        return name, []
    stat_names, meta = {}, {}
    for v in parts[5]:              # map<id, XStatMetadata{id 1, name 2}>
        f = dict(_fields(b, *dict(_fields(b, *v))[2]))
        stat_names[f.get(1, 0)] = _text(b, f.get(2, (0, 0)))
    for v in parts[4]:              # map<id, XEventMetadata{id 1, stats 5}>
        mid, stats = 0, []
        for mf, mv in _fields(b, *dict(_fields(b, *v))[2]):
            if mf == 1:
                mid = mv
            elif mf == 5:
                stats.append(dict(_fields(b, *mv)))
        meta[mid] = stats
    tf_op_id = next((i for i, n in stat_names.items() if n == "tf_op"), None)
    tf_op = {}
    for mid, stats in meta.items():
        for st in stats:                         # XStat: metadata_id 1,
            if st.get(1) == tf_op_id:            # str 5 or ref 7
                tf_op[mid] = (_text(b, st[5]) if 5 in st
                              else stat_names.get(st.get(7), ""))
    by_line = {}
    for span in parts[3]:
        lname, ts_ns, evs = "", 0, []
        for num, v in _fields(b, *span):
            if num == 2:
                lname = _text(b, v)
            elif num == 3:
                ts_ns = _signed(v)
            elif num == 4:                       # XEvent: metadata_id 1,
                f = dict(_fields(b, *v))         # offset_ps 2, duration_ps 3
                # in whole nanoseconds, as ProfileData gives them
                s = (ts_ns + f.get(2, 0) // 1000) * 1e-9
                evs.append((tf_op.get(f.get(1, 0), ""), s,
                            s + (f.get(3, 0) // 1000) * 1e-9))
        by_line.setdefault(lname, []).extend(evs)
    if "XLA Ops" in by_line:
        return name, by_line["XLA Ops"]
    return name, [ev for evs in by_line.values() for ev in evs]


def device_ops(path: str) -> dict:
    """{device plane: [(tf_op, start_s, end_s)]} of the trace at ``path``,
    on the clock of ``trace.load``'s events; planes with no timed op are
    left out, as ``trace.device_events`` leaves them."""
    with open(path, "rb") as f:
        b = f.read()
    out = {}
    for num, v in _fields(b, 0, len(b)):
        if num == 1:                             # XSpace.planes
            name, evs = _plane_ops(b, *v)
            evs = [ev for ev in evs if ev[2] > ev[1]]
            if evs:
                out[name] = evs
    return out


def context(events, ops, history) -> dict:
    """What the readers of the program's spans, counters and scopes take
    from a traced window, beside the harness's own context: the window's
    round records (``history``), ``program_idle_gaps`` and
    ``device_scopes``. ``events``: ``trace.load(path)``; ``ops``:
    ``device_ops(path)``."""
    lo, hi = window(events)
    return {"history": list(history),
            "program_idle_gaps": program_idle_gaps(events),
            "device_scopes": device_scopes(ops, lo, hi)}
