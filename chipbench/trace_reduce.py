"""Reduce a JAX profiler trace of a measured window to the benchmark's numbers.

The window is the host span ``chipbench.window`` that the harness opens around
its timed loop.  Within it, for every device plane (``/device:TPU:<i>``):

* busy time: the union of the intervals of the device's operations (events of
  its ``XLA Ops`` line), so that overlapping events count once;
* operations: the self seconds of each operation (its time less that of the
  operations nested in it, as a loop's body is nested in the loop), summed
  over the window by ``op_name``;
* idle gaps: the intervals of the window in which no operation runs, each
  named by the innermost harness span (``chipbench.*``) on the host that covers
  its middle, or ``-`` where none does;
* exposed collective time: the time in which a collective operation runs
  and no other operation but a control-flow one does
  (``metrics/exchange_exposed_share.py``).

A collective is an operation whose instruction name or opcode is
``all-to-all``, ``collective-permute``, ``all-gather``, ``all-reduce`` or
``reduce-scatter``, or one of their ``-start`` / ``-done`` halves.  The
control-flow operations (``while``, ``conditional``, ``call``) are left out of
what hides a collective, since their events span the operations of their
bodies.

Per chip numbers are averaged over the device planes.
"""

from __future__ import annotations

import collections
import functools
import glob
import os
import re
from typing import Dict, List, Tuple

WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"(all-to-all|collective-permute|all-gather|all-reduce|reduce-scatter)(-start|-done)?$")
CONTAINERS = frozenset({"while", "conditional", "call"})


def find_trace(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return merged


@functools.lru_cache(maxsize=None)
def op_name(hlo: str) -> str:
    """``%fusion.63 = f32[8,128]{1,0} fusion(...), kind=kLoop, ...`` ->
    ``fusion.63 fusion kLoop f32[8,128]``: the instruction's name, opcode,
    fusion kind and result shape (``tuple`` for a tuple), from the text the
    trace names the operation by."""
    m = re.match(r"%?(\S+) = ", hlo)
    if not m:
        return hlo
    rest = hlo[m.end():]
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:]
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{", 1)[0]
    opcode = rest.strip().split("(", 1)[0]
    kind = re.search(r"kind=(\w+)", rest)
    return " ".join([m.group(1), opcode] + ([kind.group(1)] if kind else []) + [shape])


@functools.lru_cache(maxsize=None)
def op_kind(hlo: str) -> str:
    """``collective``, ``container`` or ``other``, from the instruction's
    name without its ``.<n>`` suffix and from its opcode."""
    parts = op_name(hlo).split(" ")
    names = (re.sub(r"\.\d+$", "", parts[0]), parts[1] if len(parts) > 1 else "")
    if any(COLLECTIVE.match(x) for x in names):
        return "collective"
    return "container" if CONTAINERS.intersection(names) else "other"


def exposed_collective_ns(ops) -> Tuple[float, int]:
    """Nanoseconds of ``(name, start_ns, end_ns)`` ops in which a collective
    runs and no other operation but a control-flow one does, and the number
    of collective ops: what collectives and other ops cover together less
    what the other ops cover."""
    coll = [(s, e) for n, s, e in ops if op_kind(n) == "collective"]
    if not coll:
        return 0.0, 0
    other = [(s, e) for n, s, e in ops if op_kind(n) == "other"]
    both = sum(e - s for s, e in _union(coll + other))
    return both - sum(e - s for s, e in _union(other)), len(coll)


def host_spans(profile) -> List[Tuple[str, float, float]]:
    """The harness's spans ``(name, start_ns, end_ns)`` on every host line."""
    out = []
    for plane in profile.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(SPAN_PREFIX):
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns))
    return out


def device_ops(profile) -> Dict[str, List[Tuple[str, float, float]]]:
    """Per device plane, its operations ``(name, start_ns, end_ns)``."""
    out = {}
    for plane in profile.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = []
        for line in plane.lines:
            if line.name == OPS_LINE:
                ops.extend((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                           for ev in line.events)
        if ops:
            out[plane.name] = ops
    return out


def self_times(ops):
    """``(name, self seconds)`` of each op: its interval less those of the
    ops nested inside it."""
    out, stack = [], []  # stack of indices into out, with their end times
    for name, s, e in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            parent = stack[-1][0]
            out[parent][1] -= (min(e, stack[-1][1]) - s) / 1e9
        out.append([name, (e - s) / 1e9])
        stack.append((len(out) - 1, e))
    return out


def _label(spans, t: float) -> str:
    inside = [(e - s, name) for name, s, e in spans if s <= t <= e and name != WINDOW_SPAN]
    return min(inside)[1] if inside else "-"


def reduce_trace(profile, top: int = 10) -> Dict[str, object]:
    """``window_s``, per-chip ``busy_s``, the ``top`` operations by seconds
    (mean over chips), the ``top`` longest idle gaps by host span, per chip
    (in the order of ``busy_s``) its exposed collective seconds, and the
    number of collective ops in the window over all chips."""
    spans = host_spans(profile)
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[-1]
    planes = device_ops(profile)
    if not planes:
        raise ValueError("the trace holds no device operations")
    busy, by_name, gaps, exposed, collectives = [], collections.Counter(), [], [], 0
    for name in sorted(planes):
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in planes[name] if e > lo and s < hi]
        ns, count = exposed_collective_ns(ops)
        exposed.append(ns / 1e9)
        collectives += count
        for n, sec in self_times(ops):
            by_name[op_name(n)] += sec / len(planes)
        merged = _union([(s, e) for _, s, e in ops])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((_label(spans, (s + e) / 2), (e - s) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy,
        "chips": len(planes),
        "device_ops": [[n, s] for n, s in by_name.most_common(top)],
        "idle_gaps": [[n, s] for n, s in gaps[:top]],
        "collective_exposed_s": exposed,
        "collective_ops": collectives,
    }


def load(path: str):
    from jax.profiler import ProfileData

    return ProfileData.from_file(path)
