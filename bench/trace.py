"""Reduction of a JAX profiler trace (``.xplane.pb``) to what the
per-layer metrics read.

- Device events: the ops on each device plane (``/device:...``), from
  its ``XLA Ops`` line where the plane has one. ``busy_s`` is the length
  of the union of their intervals inside the window, averaged over the
  devices; the idle share is ``1 - busy_s / window_s``.
- ``device_ops``: the ops that took most device time, summed by name.
- ``idle_gaps``: the device's idle time inside the window, split by what
  the host was doing then: the innermost ``bench.<name>`` span open on
  the host at that moment (``outside spans`` where none was).

The window is the host span ``bench.window`` when the trace holds one,
else the extent of all events.
"""
from __future__ import annotations

from collections import defaultdict

SPAN_PREFIX = "bench."


def load(path: str):
    """[(plane, line, name, start_s, end_s)] of every timed event."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            for e in line.events:
                s = e.start_ns * 1e-9
                out.append((plane.name, line.name, e.name, s,
                            s + e.duration_ns * 1e-9))
    return out


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def op_name(text: str) -> str:
    """``%fusion.89 = f32[80,26,26,32]{...} fusion(...)`` -> ``fusion.89
    f32[80,26,26,32]``: the op and its result's type, without layouts."""
    head, _, rest = text.partition(" = ")
    if not rest:
        return text
    return f"{head.lstrip('%')} {rest.lstrip('(').split('{')[0].split(' ')[0]}"


def device_events(events):
    """{device plane: [(op name, start, end)]}, from each plane's op
    line."""
    planes = defaultdict(lambda: defaultdict(list))
    for plane, line, name, s, e in events:
        if plane.startswith("/device:") and e > s:
            planes[plane][line].append((op_name(name), s, e))
    out = {}
    for plane, lines in planes.items():
        out[plane] = lines["XLA Ops"] if "XLA Ops" in lines else [
            ev for evs in lines.values() for ev in evs]
    return out


def host_spans(events):
    return [(name[len(SPAN_PREFIX):], s, e)
            for plane, line, name, s, e in events
            if not plane.startswith("/device:")
            and name.startswith(SPAN_PREFIX)]


def _labels(spans, lo, hi):
    """Host timeline in [lo, hi] as sorted [(start, end, innermost span
    name)]. Spans of one thread nest, so the innermost open span is the
    one that opened last."""
    pts = sorted([(s, 1, i) for i, (_, s, _) in enumerate(spans)]
                 + [(e, 0, i) for i, (_, _, e) in enumerate(spans)])
    active, out, t = {}, [], lo
    for when, opening, i in pts + [(hi, 0, -1)]:
        when = min(max(when, lo), hi)
        if when > t:
            lab = (spans[max(active, key=lambda j: (active[j], j))][0]
                   if active else "outside spans")
            out.append((t, when, lab))
            t = when
        if i < 0:
            break
        if opening:
            active[i] = spans[i][1]
        else:
            active.pop(i, None)
    return out


def _overlaps(segments, gaps):
    """Yield (label, seconds) of each overlap of two sorted, internally
    disjoint interval lists."""
    i = j = 0
    while i < len(segments) and j < len(gaps):
        a, b, lab = segments[i]
        s, e = gaps[j]
        ov = min(b, e) - max(a, s)
        if ov > 0:
            yield lab, ov
        if b < e:
            i += 1
        else:
            j += 1


def reduce(events, top: int = 10) -> dict:
    spans = host_spans(events)
    win = [(s, e) for n, s, e in spans if n == "window"]
    if win:
        lo, hi = win[0]
    else:
        lo = min(s for *_, s, _ in events)
        hi = max(e for *_, e in events)
    window_s = hi - lo
    devs = device_events(events)
    busy, ops, idle = [], defaultdict(float), defaultdict(float)
    inner = [sp for sp in spans if sp[0] != "window"]
    labels = _labels(inner, lo, hi)
    for evs in devs.values():
        busy_iv = _union(_clip([(s, e) for _, s, e in evs], lo, hi))
        busy.append(sum(e - s for s, e in busy_iv))
        for name, s, e in evs:
            ops[name] += max(0.0, min(e, hi) - max(s, lo))
        gaps, t = [], lo
        for s, e in busy_iv:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        for lab, ov in _overlaps(labels, gaps):
            idle[lab] += ov / len(devs)
    busy_s = sum(busy) / len(busy) if busy else 0.0
    return {
        "window_s": window_s, "busy_s": busy_s, "devices": len(devs),
        "device_ops": sorted(ops.items(), key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(idle.items(), key=lambda kv: -kv[1])[:top],
    }
