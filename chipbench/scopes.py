"""Device seconds by the program's own scopes, and the host's turn in each
idle gap, from the ``.xplane.pb`` of a traced window.

    python3 chipbench/scopes.py chipbench/.traces/<dir>    # a kept trace
    python3 chipbench/scopes.py --run --workload g500s19-u7-2 --seed 7 --seconds 30

The second form makes ``run.py``'s traced run with the program's recorder
(``repro.obs``) on, keeps its trace, and prints the tables below and the
recorder's counters and spans after the result line.

The program names its device work with ``jax.named_scope``
(``core/table_program.py``): ``node<i>/neighbor_sum``, ``node<i>/combine``,
``node<i>/fused`` and ``node<i>/mask`` for each internal node, ``leaf``,
``root`` and ``coloring``.  Every XLA operation's event metadata on a device
plane carries the JAX op-name path as its ``tf_op`` stat
(``jit(count_batch)/vmap(node3)/neighbor_sum/while/body/...``; under ``vmap``
the outermost scope reads ``vmap(<scope>)``) and its module's ``program_id``.
``jax.profiler.ProfileData`` gives events but not metadata stats, so this
module reads the fields of ``XSpace`` it needs from the protobuf wire format.

Within the window (the host span ``chipbench.window``), for every device
plane:

* scopes: each operation's self seconds (``trace_reduce.self_times``)
  summed by its program scope, or by its XLA module's name where its op name
  holds none (the key split ``jit__threefry_split``), or ``-`` where neither
  is known;
* idle gaps: the intervals with no operation, each named by the innermost
  ``repro.*`` host span (the program's, ``repro.obs``) over its middle, else
  the innermost ``chipbench.*`` span, else ``-``.

Per chip numbers are averaged over the device planes.
"""

from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
from typing import Dict, Iterator, List, Tuple

import trace_reduce
from trace_reduce import WINDOW_SPAN, _union, self_times

PROGRAM_PREFIX = "repro."
HARNESS_PREFIX = "chipbench."
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: the scopes inside a node, and the top-level ones
NODE_OPS = ("neighbor_sum", "combine", "fused", "mask")
TOP_SCOPES = ("leaf", "root", "coloring")


# ------------------------------------------------------------- wire format
def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return x, i


def _fields(buf: bytes) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one message: an int for varint and
    fixed fields, bytes for length-delimited ones."""
    i, n = 0, len(buf)
    while i < n:
        tag, i = _varint(buf, i)
        wire = tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 1:
            v, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire == 5:
            v, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield tag >> 3, v


def _int64(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _stats(raw: List[bytes], names: Dict[int, str]) -> Dict[str, object]:
    """XStat: metadata_id 1, its value in one field: a string 5, a string
    kept as a stat metadata name 7 (ref), numbers as they come."""
    out = {}
    for buf in raw:
        f = dict(_fields(buf))
        key = names.get(f.pop(1, None), "")
        for num, v in f.items():
            out[key] = v.decode() if num == 5 else names.get(v, "") if num == 7 else v
    return out


def _plane(buf: bytes):
    """XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5.  Returns
    ``(name, lines, metadata)``: lines as ``(name, [(metadata id, start_ns,
    end_ns)])``, metadata as ``{id: (name, stats)}``."""
    name, raw_lines, raw_meta, stat_names = "", [], [], {}
    for num, v in _fields(buf):
        if num == 2:
            name = v.decode()
        elif num == 3:
            raw_lines.append(v)
        elif num == 4:
            raw_meta.append(dict(_fields(v)).get(2, b""))
        elif num == 5:
            m = dict(_fields(dict(_fields(v)).get(2, b"")))
            stat_names[m.get(1, 0)] = m.get(2, b"").decode()
    meta = {}
    for buf in raw_meta:  # XEventMetadata: id 1, display_name 2, name 4, stats 5
        mid, label, stats = 0, {}, []
        for num, v in _fields(buf):
            if num == 1:
                mid = v
            elif num in (2, 4):
                label[num] = v.decode()
            elif num == 5:
                stats.append(v)
        meta[mid] = (label.get(4) or label.get(2, ""), _stats(stats, stat_names))
    lines = []
    for buf in raw_lines:  # XLine: name 2, timestamp_ns 3, events 4
        lname, ts, events = "", 0, []
        for num, v in _fields(buf):
            if num == 2:
                lname = v.decode()
            elif num == 3:
                ts = _int64(v)
            elif num == 4:
                events.append(v)
        evs = []
        for buf in events:  # XEvent: metadata_id 1, offset_ps 2, duration_ps 3
            f = dict(_fields(buf))
            start = ts + _int64(f.get(2, 0)) / 1e3
            evs.append((f.get(1, 0), start, start + _int64(f.get(3, 0)) / 1e3))
        lines.append((lname, evs))
    return name, lines, meta


def read_space(data: bytes):
    """The planes of a serialized ``XSpace`` (planes are its field 1)."""
    return [_plane(v) for num, v in _fields(data) if num == 1]


# ---------------------------------------------------------------- reduction
def _unwrap(part: str) -> str:
    """``vmap(node3)`` -> ``node3``; ``jit(count_batch)`` -> ``count_batch``."""
    while (m := re.fullmatch(r"\w+\((.*)\)", part)):
        part = m.group(1)
    return part


def program_scope(tf_op: str) -> str:
    """The program scope of an op-name path: ``node<i>/<op>``, ``node<i>``,
    ``leaf``, ``root``, ``coloring``, or ``""`` where it holds none."""
    parts = [_unwrap(p) for p in tf_op.split("/")]
    for i, part in enumerate(parts):
        if re.fullmatch(r"node\d+", part):
            op = parts[i + 1] if i + 1 < len(parts) else ""
            return f"{part}/{op}" if op in NODE_OPS else part
        if part in TOP_SCOPES:
            return part
    return ""


def _module_names(lines, meta) -> Dict[int, str]:
    """program_id -> module name, from the modules line's
    ``jit_count_batch(<program_id>)`` events."""
    out = {}
    for lname, evs in lines:
        if lname == MODULES_LINE:
            for mid, _, _ in evs:
                m = re.fullmatch(r"(.*)\((\d+)\)", meta.get(mid, ("", {}))[0])
                if m:
                    out[int(m.group(2))] = m.group(1)
    return out


def _label(spans, t: float) -> str:
    for prefix in (PROGRAM_PREFIX, HARNESS_PREFIX):
        inside = [(e - s, name) for name, s, e in spans
                  if name.startswith(prefix) and name != WINDOW_SPAN and s <= t <= e]
        if inside:
            return min(inside)[1]
    return "-"


def reduce_space(planes) -> Dict[str, object]:
    """``window_s``, per-chip ``busy_s``, ``scopes`` (label -> self seconds),
    ``kinds`` (``neighbor_sum``, ... -> self seconds over every node),
    ``idle`` (label -> [gaps, seconds]) and ``batches`` (the harness's
    ``stream_next`` spans in the window)."""
    spans = [(meta[mid][0], s, e) for name, lines, meta in planes if name.startswith("/host:")
             for _, evs in lines for mid, s, e in evs
             if meta.get(mid, ("",))[0].startswith((PROGRAM_PREFIX, HARNESS_PREFIX))]
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = windows[-1]
    devices = [p for p in planes if p[0].startswith("/device:TPU:")
               and any(lname == OPS_LINE and evs for lname, evs in p[1])]
    if not devices:
        raise ValueError("the trace holds no device operations")
    busy, by_scope, idle = [], collections.Counter(), collections.defaultdict(lambda: [0, 0.0])
    for _, lines, meta in devices:
        modules = _module_names(lines, meta)
        ops = []
        for lname, evs in lines:
            if lname != OPS_LINE:
                continue
            for mid, s, e in evs:
                if e <= lo or s >= hi:
                    continue
                stats = meta.get(mid, ("", {}))[1]
                label = (program_scope(str(stats.get("tf_op", "")))
                         or modules.get(stats.get("program_id"), "-"))
                ops.append((label, max(s, lo), min(e, hi)))
        for label, sec in self_times(ops):
            by_scope[label] += sec / len(devices)
        merged = _union([(s, e) for _, s, e in ops])
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gap = idle[_label(spans, (s + e) / 2)]
                gap[0] += 1 / len(devices)
                gap[1] += (e - s) / 1e9 / len(devices)
    kinds = collections.Counter()
    for label, sec in by_scope.items():
        kinds[label.rpartition("/")[2] if label.startswith("node") else label] += sec
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": busy,
        "scopes": dict(by_scope.most_common()),
        "kinds": dict(kinds.most_common()),
        "idle": {k: v for k, v in sorted(idle.items(), key=lambda kv: -kv[1][1])},
        "batches": sum(name == HARNESS_PREFIX + "stream_next" and lo <= s and e <= hi
                       for name, s, e in spans),
    }


def reduce_file(path: str) -> Dict[str, object]:
    """:func:`reduce_space` of a trace file."""
    with open(path, "rb") as f:
        return reduce_space(read_space(f.read()))


def print_tables(red) -> None:
    """The scope and idle tables of a reduction, as log lines."""
    busy = sum(red["busy_s"]) / len(red["busy_s"])
    for label, sec in red["scopes"].items():
        print(f"[scopes] {label} {sec:.6f} s, {100 * sec / busy:.4f}% of busy", flush=True)
    for label, (gaps, sec) in red["idle"].items():
        print(f"[idle] {label}: {gaps:g} gaps, {sec:.6f} s, "
              f"{sec / max(red['batches'], 1):.6f} s per batch", flush=True)


def print_obs(snap) -> None:
    """The program's counters, and its spans summed by name, as log lines."""
    print(f"[obs] counters {json.dumps(snap['counters'], sort_keys=True)}", flush=True)
    totals: Dict[str, List[float]] = collections.defaultdict(lambda: [0, 0.0])
    for name, _, start, end, _ in snap["spans"]:
        totals[name][0] += 1
        totals[name][1] += (end - start) / 1e9
    for name, (n, sec) in sorted(totals.items()):
        print(f"[obs] span {name}: {n:g} in {sec:.6f}s", flush=True)


def traced_run(argv: List[str]) -> int:
    """``run.py``'s traced run with the program's recorder on: its spans
    land in the trace beside the device ops, the trace is kept, and its
    tables and the recorder's snapshot are printed after the result line."""
    import run

    sys.path.insert(0, os.path.join(run.ROOT, "src"))
    try:
        from repro import obs
    except ImportError:  # a program without the recorder: scopes only
        obs = None
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--trace-dir", default="")
    args, rest = ap.parse_known_args(argv)
    log_dir = args.trace_dir or os.path.join(run.TRACE_DIR, f"{args.workload}-{args.seed}")
    if obs is not None:
        obs.enable()
    code = run.main(["--workload", args.workload, "--seed", str(args.seed), "--trace", "1",
                     "--trace-dir", log_dir] + rest)
    if code == 0:
        print_tables(reduce_file(trace_reduce.find_trace(log_dir)))
        if obs is not None:
            print_obs(obs.snapshot())
    return code


def main(argv=None) -> int:
    """``scopes.py TRACE`` prints the tables of a kept trace (a
    ``.xplane.pb`` or a directory that holds one, as ``run.py --trace-dir``
    leaves it); ``scopes.py --run --workload W --seed N --seconds S`` makes
    the traced run itself (:func:`traced_run`)."""
    argv = sys.argv[1:] if argv is None else argv
    if argv[:1] == ["--run"]:
        return traced_run(argv[1:])
    ap = argparse.ArgumentParser(description=main.__doc__)
    ap.add_argument("trace")
    path = ap.parse_args(argv).trace
    print_tables(reduce_file(path if os.path.isfile(path) else trace_reduce.find_trace(path)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
