"""What the program under test records about itself, reduced for the
per-layer readers.

- Host spans: ``run.program_spans``, the program's own records ``(name,
  start, dur, key, attrs)`` (``repro.serve.tracing``) from the traced
  window, shifted onto the trace's clock by the marker's offset, as the
  harness's own spans are. ``detect.stage``/``detect.wait`` are keyed by
  dispatch number, ``sched.queue`` by request id.
- Layer scopes: ``run.op_scopes``, {HLO instruction name: layer scope} of
  the served executable, from ``op_scopes`` over its compiled text. Every
  layer of the bundle runs under a ``jax.named_scope`` of its name; the
  compiled HLO keeps the scope in each instruction's ``op_name`` metadata,
  while the trace names an op by its instruction alone.

A run that carries neither (a program that records no spans, or a harness
that does not pass them on) makes every reader of this module return
nothing.
"""
from __future__ import annotations

import collections
import re

from bench.core import trace

OTHER = "other"                 # ops under no layer scope
POST_SCOPES = ("decode", "nms", "wire")
QUEUE = "sched.queue"           # a request's wait: not something the host does
_INSTR = re.compile(r'\s*(?:ROOT )?%?([\w.\-]+) = .*?metadata=\{[^}]*?'
                    r'op_name="([^"]*)"')


def layer_scopes(cfg: dict) -> tuple:
    """The scope names of the bundle: the configuration's layers, then
    post-processing."""
    return tuple(row[0] for row in cfg["layers"]) + POST_SCOPES


def scope_of(op_name: str, scopes) -> str:
    """The first component of an ``op_name`` path that is a layer scope:
    ``"jit(_bundle)/conv1/jit(relu)/max"`` -> ``"conv1"``."""
    for part in op_name.split("/"):
        if part in scopes:
            return part
    return OTHER


def op_scopes(hlo_text: str, scopes) -> dict:
    """{instruction name: layer scope} of a compiled module's text
    (``compiled.as_text()``); an instruction without ``op_name`` is left
    out, and counts as ``OTHER``."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            out[m.group(1)] = scope_of(m.group(2), scopes)
    return out


def scope_seconds(tr: dict, execs: list, scopes: dict) -> collections.Counter:
    """Device seconds per layer scope over the ops of ``execs``."""
    acc = collections.Counter()
    for ops in trace.ops_in(tr, execs):
        for name, _, _, dur in ops:
            acc[scopes.get(name, OTHER)] += dur
    return acc


def spans(run, name: str) -> list:
    """The program's spans called ``name`` that start in the traced
    window; [] where the run carries none."""
    got = getattr(run, "program_spans", None)
    if not got or run.trace_window is None:
        return []
    lo, hi = run.trace_window
    return [sp for sp in got if sp[0] == name and lo <= sp[1] < hi]


def host_spans(program_spans) -> list:
    """The program's spans as ``(name, start, dur)`` for
    ``trace.idle_by_host_state``, without the requests' queue waits."""
    return [(n, s, d) for n, s, d, *_ in program_spans if n != QUEUE]


def idle_inside(tr: dict, intervals, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which the first device was idle and one of
    ``intervals`` (start, end) was open."""
    cover = trace.merge(trace.clip(intervals, lo, hi))
    total, i = 0.0, 0
    for gs, ge in trace.gaps(tr, lo, hi):        # both sorted, disjoint
        while i < len(cover) and cover[i][1] <= gs:
            i += 1
        j = i
        while j < len(cover) and cover[j][0] < ge:
            total += min(ge, cover[j][1]) - max(gs, cover[j][0])
            j += 1
    return total
