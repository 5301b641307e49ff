"""From a profiler trace of the window to device time per program stage.

The program marks its tick program's stages with ``jax.named_scope``
(``knn.reindex``, ``knn.sweep``, ``knn.gather`` ...) and its host
boundaries with ``knn.*`` spans (``repro/tracing.py``).  This module reads
both back from the trace the harness records, on the trace's one clock:

* each device op gets a stage: the innermost ``knn.`` scope on its
  ``op_name`` path; for an op of a program that has no scoped op at all
  (``jit_rebuild_zmap``, a result slice) that program's name; and
  ``unscoped`` for an op of a scoped program outside every scope;
* ``stages``: ``{stage: self seconds}`` averaged over the devices, the
  ``unscoped`` ops apart in ``unscoped_s``; the two add up to the busy time
  (``knnbench.trace``), since self times of nested ops add up to their
  union;
* ``program_spans``: ``{name: [total_s, count]}`` of the ``knn.*`` spans
  inside the window (name without the prefix);
* ``unscoped_ops``: the five ``unscoped`` ops with most self time;
* ``device_ops`` and ``idle_gaps`` as ``knnbench.trace`` gives them, each
  op labelled ``<op> @<stage>`` and each gap named ``<harness span>/<program
  span>``: the harness span that overlaps it most, and the program span
  found by descending from the one that overlaps it most into the nested
  span that overlaps it most, for as long as one does (``session.submit``
  holds ``session.finalize`` and ``session.dispatch``).

Where an op's ``op_name`` comes from: a stat of its event that holds a
``knn.`` path where the trace has one, else the ``metadata={op_name=...}``
of its instruction in the executable's HLO text (``hlo_texts``), matched by
program and instruction name.  A trace of a program with no ``knn.`` scope
(one built before the scopes existed) gives no ``stages`` key.
"""
from __future__ import annotations

import re
from collections import defaultdict

from . import trace as trace_mod

__all__ = ["SCOPE", "UNSCOPED", "stage_of", "hlo_op_names", "reduce_stages",
           "reduce_xplane_stages", "tpu_op_event", "cpu_op_event"]

SCOPE = "knn."
UNSCOPED = "unscoped"

_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_INSTR = re.compile(r"^\s*(ROOT )?%?([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")
_CALLEE = re.compile(r"\b(?:calls|body|condition|to_apply)=%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")


def stage_of(path: str | None) -> str | None:
    """The innermost ``knn.`` scope of an ``op_name`` path, without its
    prefix; None where the path has none."""
    if not path:
        return None
    found = None
    for part in path.split("/"):
        part = part.split(":")[0]
        if part.startswith(SCOPE) and len(part) > len(SCOPE):
            found = part[len(SCOPE):]
    return found


def _parse_hlo(lines):
    """``{computation: [(instruction, op_name, callees, is_root)]}``."""
    comps, current = {}, None
    for line in lines:
        c = _COMPUTATION.match(line)
        if c:
            current = comps.setdefault(c.group(1), [])
            continue
        i = _INSTR.match(line)
        if i and current is not None:
            rest = i.group(3)
            op = _OP_NAME.search(rest)
            callees = _CALLEE.findall(rest)
            for group in _BRANCHES.findall(rest):
                callees += [c.strip().lstrip("%") for c in group.split(",")]
            current.append((i.group(2), op.group(1) if op else None,
                            callees, bool(i.group(1))))
    return comps


def hlo_op_names(texts) -> dict:
    """``{program: {instruction: op_name}}`` from compiled HLO texts.

    The TPU's compiler leaves some instructions without metadata.  A fusion
    takes the ``op_name`` of the computation it calls: that of its root,
    else of the last instruction there that has one (XLA's convention for
    naming a fusion after its root).  Any other instruction without one
    takes that of the instruction whose loop body or branch holds it.
    """
    out = {}
    for text in texts:
        lines = text.splitlines()
        m = _MODULE.match(lines[0]) if lines else None
        if not m:
            continue
        comps = _parse_hlo(lines[1:])
        caller = {}
        for comp, instrs in comps.items():
            for name, _, callees, _ in instrs:
                for callee in callees:
                    caller.setdefault(callee, (comp, name))

        def inner(comp, seen):
            instrs = comps.get(comp, [])
            for name, op, callees, _ in ([x for x in instrs if x[3]]
                                         + instrs[::-1]):
                found = op or next((inner(c, seen | {comp})
                                    for c in callees if c not in seen), None)
                if found:
                    return found
            return None

        table = {}
        for comp, instrs in comps.items():
            for name, op, callees, _ in instrs:
                found = op or next(
                    (f for f in (inner(c, {comp}) for c in callees) if f),
                    None)
                if found:
                    table[name] = found

        def context(comp, seen=frozenset()):
            if comp not in caller or comp in seen:
                return None
            up, name = caller[comp]
            return table.get(name) or context(up, seen | {comp})

        for comp, instrs in comps.items():
            for name, _, _, _ in instrs:
                if name not in table:
                    found = context(comp)
                    if found:
                        table[name] = found
        out[m.group(1)] = table
    return out


def _base_name(name: str) -> str:
    """A span or program name without ``#...`` arguments or ``(id)``."""
    return re.sub(r"\(\d+\)$", "", name.split("#")[0])


def _innermost(spans, g0, g1) -> str:
    """The program span a gap falls in, descending through nested spans
    by most overlap; ``"none"`` where no span overlaps it."""
    name, outer = "none", None
    while True:
        cands = [(min(e, g1) - max(s, g0), s - e, n, s, e)
                 for n, s, e in spans
                 if outer is None or (outer[0] <= s and e <= outer[1]
                                      and (s, e) != outer)]
        cands = [c for c in cands if c[0] > 0]
        if not cands:
            return name
        _, _, name, s, e = max(cands)
        outer = (s, e)


def reduce_stages(device_ops, harness_spans, program_spans, window) -> dict:
    """Stage self times, program spans and the labelled breakdown.

    ``device_ops``: ``{device: [(op, program, op_path, start_s, end_s)]}``
    (``op_path`` None where unknown); ``harness_spans`` and
    ``program_spans``: ``[(name, start_s, end_s)]``; ``window``:
    ``(start_s, end_s)``; one clock.
    """
    lo, hi = window
    scoped = {prog for ops in device_ops.values()
              for _, prog, path, _, _ in ops if stage_of(path)}
    n_dev = max(1, len(device_ops))
    h_spans = [(n, s, e) for n, s, e in harness_spans if e > lo and s < hi]
    p_spans = [(n, s, e) for n, s, e in program_spans if e > lo and s < hi]
    stage_t = defaultdict(float)
    label_t = defaultdict(float)
    gaps = []
    for ops in device_ops.values():
        inside = []
        for op, prog, path, s, e in ops:
            if e <= lo or s >= hi:
                continue
            st = stage_of(path) or (UNSCOPED if prog in scoped
                                    else prog or "unknown")
            inside.append(((op, st), max(s, lo), min(e, hi)))
        for (op, st), t in trace_mod._self_times(inside).items():
            stage_t[st] += t
            label_t[f"{op} @{st}"] += t
        busy = trace_mod.merge([(s, e) for _, s, e in inside])
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for i in range(0, len(edges), 2):
            g0, g1 = edges[i], edges[i + 1]
            if g1 > g0:
                name = (f"{trace_mod._cover(h_spans, g0, g1)}/"
                        f"{_innermost(p_spans, g0, g1)}")
                gaps.append((g1 - g0, name))
    gaps.sort(key=lambda g: -g[0])
    totals = defaultdict(lambda: [0.0, 0])
    for name, s, e in p_spans:
        totals[name][0] += min(e, hi) - max(s, lo)
        totals[name][1] += 1
    out = dict(
        program_spans={n: v for n, v in sorted(totals.items())},
        device_ops=[[n, t / n_dev] for n, t in sorted(
            label_t.items(), key=lambda kv: -kv[1])[:trace_mod.OPS_KEPT]],
        idle_gaps=[[n, g] for g, n in gaps[:trace_mod.GAPS_KEPT]],
    )
    if scoped:
        out["unscoped_s"] = stage_t.pop(UNSCOPED, 0.0) / n_dev
        out["unscoped_ops"] = [[n, t / n_dev] for n, t in sorted(
            label_t.items(), key=lambda kv: -kv[1])
            if n.endswith(" @" + UNSCOPED)][:5]
        out["stages"] = {n: t / n_dev for n, t in sorted(
            stage_t.items(), key=lambda kv: -kv[1])}
    return out


def tpu_op_event(plane: str, line: str, event) -> bool:
    """An op of a TPU device plane in a ``jax.profiler`` trace."""
    return trace_mod.tpu_op_line(plane, line)


def cpu_op_event(plane: str, line: str, event) -> bool:
    """An XLA op run by the CPU backend (the tests' stand-in device)."""
    return plane == "/host:CPU" and any(k == "hlo_op" for k, _ in event.stats)


def _event_path(stats: dict) -> str | None:
    for val in stats.values():
        if isinstance(val, str) and stage_of(val):
            return val
    return None


def reduce_xplane_stages(path, *, window_span: str, hlo_texts=(),
                         harness_prefix: str = "bench.",
                         device_event=tpu_op_event):
    """``reduce_stages`` over an ``.xplane.pb``; None if it has no window.

    Device ops are the events ``device_event(plane, line, event)`` accepts,
    one device per plane.  An op's program is its ``hlo_module`` stat, else
    the event of the plane's ``XLA Modules`` line it starts in; its
    instruction is its ``hlo_op`` stat, else the head of its name (the HLO
    text ``%fusion.194 = ...``).
    """
    import jax

    names = hlo_op_names(hlo_texts)
    data = jax.profiler.ProfileData.from_file(str(path))
    raw = defaultdict(list)
    modules = defaultdict(list)
    harness, program = [], []
    window = None
    for plane in data.planes:
        for line in plane.lines:
            for ev in line.events:
                s = ev.start_ns * 1e-9
                e = s + ev.duration_ns * 1e-9
                if line.name == "XLA Modules":
                    modules[plane.name].append((_base_name(ev.name), s, e))
                    continue
                if device_event(plane.name, line.name, ev):
                    raw[plane.name].append((ev.name, dict(ev.stats), s, e))
                elif ev.name == window_span:
                    window = (s, e)
                elif ev.name.startswith(harness_prefix):
                    harness.append((ev.name[len(harness_prefix):], s, e))
                elif ev.name.startswith(SCOPE):
                    program.append(
                        (_base_name(ev.name)[len(SCOPE):], s, e))
    if window is None:
        return None
    device_ops = {}
    for dev, evs in raw.items():
        mods = sorted(modules.get(dev, []), key=lambda m: m[1])
        ops = []
        for name, stats, s, e in evs:
            prog = stats.get("hlo_module")
            if prog is None:
                prog = next((m for m, ms, me in mods if ms <= s < me), None)
            instr = str(stats.get("hlo_op") or name.partition(" = ")[0]
                        ).lstrip("%")
            op_path = _event_path(stats) or names.get(prog, {}).get(instr)
            ops.append((trace_mod.op_label(name), prog, op_path, s, e))
        device_ops[dev] = ops
    return reduce_stages(device_ops, harness, program, window)
