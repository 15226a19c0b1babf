"""Reduction of a profiler trace to the benchmark's device numbers.

The harness wraps each traced ``partition()`` call in a
``jax.profiler.TraceAnnotation`` named ``ANNOTATION``; that host span
puts the call's interval on the trace's clock. ``load_events`` turns an
``.xplane.pb`` file into plain event tuples, and ``reduce_events``
computes everything from those tuples, so the arithmetic can be tested
on a small recorded excerpt without JAX.

An event is ``(plane, line, name, start_ns, end_ns)``. Device planes are
those whose name starts with ``/device:``; on them only the op-level
line (``XLA Ops``) counts as device work, which leaves out the module
and step lines that merely bracket the same ops. Host spans are read
from the thread that holds the annotation.
"""
from __future__ import annotations

import re
from collections import defaultdict

ANNOTATION = "bench.partition"
DEVICE_PREFIX = "/device:"
OP_LINE = "XLA Ops"
UNTRACED = "untraced host work"


def load_events(path: str) -> list:
    """Plain event tuples of one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.end_ns)))
    return out


def _union(intervals: list) -> list:
    merged: list = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def op_label(hlo: str) -> str:
    """A device op's label: its HLO text without layouts, shortened.

    The trace names an op by its whole HLO instruction
    (``%fusion.2 = s32[524288]{0:T(1024)S(1)} fusion(...)``); the label
    keeps the instruction and its shapes, enough to tell a gather from
    a kernel, and drops the layout braces.
    """
    text = hlo.lstrip("%")
    prev = None
    while prev != text:
        prev, text = text, re.sub(r"\{[^{}]*\}", "", text)
    return text[:120]


def _self_segments(spans: list) -> list:
    """Self time of properly nested spans on one thread.

    ``spans`` are ``(start, end, name)``; returns disjoint
    ``(start, end, name)`` pieces, each where ``name`` was the
    innermost open span.
    """
    out: list = []
    stack: list = []        # [start, end, name, cursor]

    def close(top):
        if top[3] < top[1]:
            out.append((top[3], top[1], top[2]))

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        while stack and stack[-1][1] <= a:
            close(stack.pop())
        if stack:
            parent = stack[-1]
            b = min(b, parent[1])
            if a > parent[3]:
                out.append((parent[3], a, parent[2]))
            parent[3] = max(parent[3], b)
        stack.append([a, b, name, a])
    while stack:
        close(stack.pop())
    out.sort()
    return out


def _attribute(gaps: list, pieces: list) -> dict:
    """Sum each gap's time by the host piece that covers it."""
    by_name: dict = defaultdict(float)
    j = 0
    for ga, gb in gaps:
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= ga:
            j += 1
        i = j
        while i < len(pieces) and pieces[i][0] < gb:
            a, b, name = pieces[i]
            c = min(b, gb) - max(a, ga)
            if c > 0:
                by_name[name] += c
                covered += c
            i += 1
        if gb - ga - covered > 0:
            by_name[UNTRACED] += gb - ga - covered
    return by_name


def reduce_events(events: list, kernels=("hype_score",),
                  top: int = 10) -> dict | None:
    """Device busy time, kernel time and idle gaps of the traced call.

    Returns None when the trace holds no annotated call or no device op
    inside it. Otherwise a dict, all times in seconds:

    * ``window_s``: length of the annotated call;
    * ``busy_s``: union of op intervals inside it, averaged over the
      device planes that ran an op;
    * ``kernel_s``: summed durations of ops whose name contains one of
      ``kernels``, averaged the same way;
    * ``device_ops``: the ``top`` op labels by summed device time;
    * ``idle_gaps``: the device's idle time inside the call, summed by
      the innermost span open on the annotated host thread at each
      moment (the profiler's Python function spans where it records
      them), ``UNTRACED`` where none is.
    """
    ann = [(p, line, a, b) for p, line, name, a, b in events
           if name == ANNOTATION and not p.startswith(DEVICE_PREFIX)]
    if not ann:
        return None
    host_plane, host_line = ann[0][0], ann[0][1]
    lo, hi = min(x[2] for x in ann), max(x[3] for x in ann)
    per_dev: dict = defaultdict(list)
    host: list = []
    for p, line, name, a, b in events:
        if b <= lo or a >= hi:
            continue
        if p.startswith(DEVICE_PREFIX) and line == OP_LINE:
            per_dev[p].append((name, max(a, lo), min(b, hi)))
        elif p == host_plane and line == host_line and name != ANNOTATION:
            host.append((max(a, lo), min(b, hi), name))
    if not per_dev:
        return None
    busy = kernel = 0.0
    op_time: dict = defaultdict(float)
    gaps: list = []
    for ops in per_dev.values():
        merged = _union([(a, b) for _, a, b in ops])
        busy += sum(b - a for a, b in merged)
        for name, a, b in ops:
            label = op_label(name)
            op_time[label] += b - a
            if any(k in label.split(" ")[0] for k in kernels):
                kernel += b - a
        edges = [lo] + [x for ab in merged for x in ab] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    ndev = len(per_dev)
    gap_time = _attribute(sorted(gaps), _self_segments(host))
    ns = 1e-9
    return {
        "window_s": (hi - lo) * ns,
        "busy_s": busy / ndev * ns,
        "kernel_s": kernel / ndev * ns,
        "device_ops": [[k, v / ndev * ns] for k, v in sorted(
            op_time.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v / ndev * ns] for k, v in sorted(
            gap_time.items(), key=lambda kv: -kv[1])[:top]],
    }
