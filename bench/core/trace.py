"""From a profiler trace to the numbers the per-layer readers need.

The run traces the device only: host tracing is off, because on a TPU host
the per-frame uploads emit hundreds of thousands of host events a second
and slow the host path severalfold. ``load`` keeps two lists of plain
tuples, so that the reduction below can be checked on a small recorded
trace without JAX:

- ``ops``: device operations ``(device, text, start, dur)`` from each
  device's ``XLA Ops`` line; ``text`` is the HLO instruction, from which
  ``split_hlo`` takes its name (``fusion.29``, ``_w1a8_conv3x3_pool.4``)
  and opcode (``fusion``, ``custom-call``);
- ``modules``: executions of compiled programs ``(device, name, start,
  dur)`` from each device's ``XLA Modules`` line.

Times are seconds on the trace's clock. The benchmark's host spans are
kept in memory by the feeder on the host's clock; ``offset`` maps the host
clock onto the trace's from one marker program that the run executes just
before its window.
"""
from __future__ import annotations

import bisect
import collections

MARKER = "jit_bench_marker"     # module name of the alignment marker
WALK = 64          # spans looked back through for the one open at a time
UNTRACED = "untraced host"


def split_hlo(text: str) -> tuple:
    """An ``XLA Ops`` event name (the HLO instruction's text) ->
    (instruction name, opcode):
    ``"%fusion.29 = f32[8,128]{1,0:T(8,128)} fusion(...)"`` ->
    ("fusion.29", "fusion"). The result shape may hold parentheses (tile
    layouts, tuples), so the opcode is the word after the first space
    outside any bracket."""
    name, _, rest = text.partition(" = ")
    depth = 0
    for i, ch in enumerate(rest):
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            return name.lstrip("%"), rest[i + 1:].split("(", 1)[0]
    return name.lstrip("%"), ""


def load(path: str) -> dict:
    """The device ops and module executions of a trace file."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules = [], []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name == "XLA Ops":
                dest = ops
            elif line.name == "XLA Modules":
                dest = modules
            else:
                continue
            for ev in line.events:
                dest.append((plane.name, ev.name, ev.start_ns * 1e-9,
                             ev.duration_ns * 1e-9))
    return {"ops": ops, "modules": modules}


def offset(tr: dict, host_start: float) -> float:
    """Trace time minus host time, from the first marker execution, which
    the host started at ``host_start`` (launch latency, tens of
    microseconds, is the error)."""
    starts = [s for _, name, s, _ in tr["modules"] if name.startswith(MARKER)]
    if not starts:
        raise ValueError("the trace holds no marker execution")
    return min(starts) - host_start


def merge(intervals) -> list:
    """Union of (start, end) intervals, as sorted disjoint intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _by_device(tr: dict) -> dict:
    per_dev = collections.defaultdict(list)
    for dev, _, s, d in tr["ops"]:
        per_dev[dev].append((s, s + d))
    return per_dev


def busy(tr: dict, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some operation ran, averaged over the
    devices that ran any."""
    per_dev = _by_device(tr)
    if not per_dev:
        return 0.0
    total = sum(sum(e - s for s, e in merge(clip(iv, lo, hi)))
                for iv in per_dev.values())
    return total / len(per_dev)


def gaps(tr: dict, lo: float, hi: float) -> list:
    """Idle stretches (start, end) inside [lo, hi] of the first device."""
    per_dev = _by_device(tr)
    iv = merge(clip(per_dev[min(per_dev)], lo, hi)) if per_dev else []
    out, t = [], lo
    for s, e in iv:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def host_state(spans: list, starts: list, t: float) -> str:
    """The innermost host span open at time ``t``, or ``UNTRACED``.
    ``spans`` are (name, start, dur) sorted by start, and ``starts`` their
    start times; spans nest, so the innermost is the latest-starting one
    that still covers ``t``."""
    i = bisect.bisect_right(starts, t) - 1
    for name, s, d in spans[max(0, i - WALK):i + 1][::-1]:
        if s + d > t:
            return name
    return UNTRACED


def idle_by_host_state(tr: dict, spans: list, lo: float, hi: float) -> list:
    """Idle seconds of the device grouped by the innermost host span open
    at the middle of each gap, longest first: [[state, seconds]]. ``spans``
    are (name, start, dur) on the trace's clock."""
    spans = sorted(spans, key=lambda sp: sp[1])
    starts = [s for _, s, _ in spans]
    acc = collections.Counter()
    for s, e in gaps(tr, lo, hi):
        acc[host_state(spans, starts, (s + e) / 2)] += e - s
    return [[k, v] for k, v in acc.most_common()]


def modules_named(tr: dict, prefix: str, lo: float, hi: float) -> list:
    """Executions (device, start, end) of programs whose name starts with
    ``prefix``, wholly inside [lo, hi]."""
    return [(dev, s, s + d) for dev, name, s, d in tr["modules"]
            if name.startswith(prefix) and s >= lo and s + d <= hi]


def ops_in(tr: dict, execs: list) -> list:
    """The ops of each execution: [[(name, opcode, start, dur)]], in start
    order, for executions (device, start, end)."""
    by_dev = collections.defaultdict(list)
    for dev, text, s, d in tr["ops"]:
        by_dev[dev].append((s, d, text))
    starts = {}
    for dev, v in by_dev.items():
        v.sort()
        starts[dev] = [s for s, *_ in v]
    out = []
    for dev, s0, e0 in execs:
        v = by_dev[dev]
        i = bisect.bisect_left(starts[dev], s0)
        j = bisect.bisect_right(starts[dev], e0)
        out.append([split_hlo(text) + (s, d) for s, d, text in v[i:j]
                    if s + d <= e0])
    return out


def is_kernel(opcode: str) -> bool:
    """A Pallas kernel: on a TPU it runs as a ``custom-call``."""
    return opcode == "custom-call"


def label(text: str) -> str:
    """A short label of an HLO instruction: its name, opcode and result
    shape without the layout: ``"fusion.29 fusion f32[32,320,320,16]"``."""
    name, opcode = split_hlo(text)
    shape = text.partition(" = ")[2].split(" " + opcode + "(", 1)[0]
    out, depth = [], 0
    for ch in shape:                       # drop {layout} groups
        depth += ch == "{"
        if not depth:
            out.append(ch)
        depth -= ch == "}"
    return f"{name} {opcode} {''.join(out)}"


def top_ops(tr: dict, lo: float, hi: float, n: int = 10) -> list:
    """The device operations that took most time in [lo, hi]:
    [[label, seconds]], summed over their executions."""
    acc = collections.Counter()
    for _, text, s, d in tr["ops"]:
        if s >= lo and s + d <= hi:
            acc[label(text)] += d
    return [[k, v] for k, v in acc.most_common(n)]
