"""From a profiler trace of the window to busy time, idle gaps and top ops.

The pure part (``reduce_events``) works on plain intervals, so a test can
check it on a small trace recorded on the CPU; ``reduce_xplane`` reads the
``.xplane.pb`` that ``jax.profiler`` writes and hands it the device ops and
the harness's own host spans.

* busy: the union of the intervals in which an operation ran on a device,
  inside the window, averaged over the devices;
* idle gaps: the holes in that union on each device, each named after the
  host span that covers most of it (``"none"`` where no span does), which
  says what the host was doing while the device waited;
* top ops: each op name's self time (its duration less that of the ops
  nested inside it on the same line), averaged over the devices.
"""
from __future__ import annotations

import re
from collections import defaultdict

__all__ = ["merge", "reduce_events", "reduce_xplane", "tpu_op_line"]

GAPS_KEPT = 10
OPS_KEPT = 10


def merge(intervals):
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _self_times(ops):
    """Self time per op name on one line: nested children subtracted."""
    out = defaultdict(float)
    stack = []  # (end, name) of the events still open, innermost last
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out[name] += e - s
        if stack:
            out[stack[-1][1]] -= e - s
        stack.append((e, name))
    return out


def reduce_events(device_ops, host_spans, window):
    """Busy time, idle gaps and top ops of one traced window.

    ``device_ops``: ``{device: [(name, start_s, end_s), ...]}`` (each
    device's op line); ``host_spans``: ``[(name, start_s, end_s), ...]``;
    ``window``: ``(start_s, end_s)``.  Times in seconds on one clock.
    """
    lo, hi = window
    n_dev = max(1, len(device_ops))
    busy_total = 0.0
    gaps = []
    op_time = defaultdict(float)
    spans = [(n, s, e) for n, s, e in host_spans if e > lo and s < hi]
    for dev, ops in device_ops.items():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in ops
                  if e > lo and s < hi]
        busy = merge([(s, e) for _, s, e in inside])
        busy_total += sum(e - s for s, e in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for i in range(0, len(edges), 2):
            g0, g1 = edges[i], edges[i + 1]
            if g1 > g0:
                gaps.append((g1 - g0, _cover(spans, g0, g1), dev))
        for name, t in _self_times(inside).items():
            op_time[name] += t
    gaps.sort(key=lambda g: -g[0])
    ops = sorted(op_time.items(), key=lambda kv: -kv[1])
    window_s = hi - lo
    busy_s = busy_total / n_dev
    return dict(
        window_s=window_s,
        busy_s=busy_s,
        idle_share=1.0 - busy_s / window_s if window_s > 0 else None,
        idle_gaps=[[name, g] for g, name, _ in gaps[:GAPS_KEPT]],
        device_ops=[[name, t / n_dev] for name, t in ops[:OPS_KEPT]],
        devices=n_dev,
    )


def _cover(spans, g0, g1) -> str:
    """The host span that overlaps ``[g0, g1]`` most; innermost on ties."""
    best, best_len, best_width = "none", 0.0, float("inf")
    for name, s, e in spans:
        ov = min(e, g1) - max(s, g0)
        width = e - s
        if ov > best_len or (ov == best_len and ov > 0 and width < best_width):
            best, best_len, best_width = name, ov, width
    return best


def tpu_op_line(plane_name: str, line_name: str) -> bool:
    """The op line of a TPU device plane in a ``jax.profiler`` trace."""
    return plane_name.startswith("/device:TPU:") and line_name == "XLA Ops"


def op_label(text: str) -> str:
    """A short name for an XLA op event, whose name is its HLO text:
    ``"%fusion.194 = s32[2097152]{...} fusion(...)"`` becomes
    ``"fusion.194 fusion s32[2097152]"``."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text[:120]
    m = re.search(r" ([a-z][\w-]*)\(", rest)
    kind = m.group(1) if m else "?"
    shape = ("tuple" if rest.startswith("(")
             else rest.split("{")[0].split(" ")[0])
    return f"{head.lstrip('%')} {kind} {shape}"[:120]


def reduce_xplane(path, *, window_span: str, span_prefix: str = "bench.",
                  device_line=tpu_op_line):
    """``reduce_events`` over an ``.xplane.pb``; None if it has no window.

    The window is the host span named ``window_span``; the host spans are
    those whose names start with ``span_prefix`` (the harness's own), the
    window span excepted; device ops are the events of every line that
    ``device_line(plane, line)`` accepts, one device per plane.
    """
    import jax

    data = jax.profiler.ProfileData.from_file(str(path))
    device_ops = defaultdict(list)
    spans = []
    window = None
    for plane in data.planes:
        for line in plane.lines:
            is_dev = device_line(plane.name, line.name)
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if is_dev:
                    device_ops[plane.name].append((op_label(ev.name), s, e))
                elif ev.name == window_span:
                    window = (s, e)
                elif ev.name.startswith(span_prefix):
                    spans.append((ev.name[len(span_prefix):], s, e))
    if window is None:
        return None
    out = reduce_events(dict(device_ops), spans, window)
    out["events"] = sum(len(v) for v in device_ops.values())
    return out
