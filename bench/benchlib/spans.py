"""A traced solve reduced by the names the program gives its layers.

``repro.core.obs`` names them: host spans ``lpa.*`` around the solve
loop's phases (``lpa.solve``, ``lpa.iteration``, ``lpa.dispatch.move``,
``lpa.dispatch.frontier``, ``lpa.sync.<what>``) and device scopes in each
op's ``op_name`` (``jit(lpa_move)/lpa.relayout/gather``: ``lpa.gather``,
``lpa.relayout``, ``lpa.fold.r<i>``, ``lpa.apply``).

A TPU's op events carry no ``op_name``: an op event's name is its HLO
instruction's text, and the instruction's ``op_name`` is in the compiled
module's text. So :func:`with_scopes` maps (module, instruction) to
``op_name`` with the texts of the modules it is given (:func:`op_names_of`).

* :func:`scope_s`: device seconds of the ops in a scope;
* :func:`idle_by_span`: the device's idle time inside a window, each idle
  instant charged to the innermost ``lpa.*`` host span that covers it;
* :func:`sync_count`: the ``lpa.sync.*`` spans, one per host read.

A program without these names (an older commit) gives empty results.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import gzip
import json
import re

from benchlib import trace as tr

PREFIX = "lpa."
_INSTRUCTION = re.compile(r"^%?([\w.\-]+) = ")
_OP_NAME = re.compile(r'\s+(?:ROOT )?%([\w.\-]+) = .*op_name="([^"]*)"')


@dataclasses.dataclass(frozen=True)
class ScopedEvent(tr.Event):
    scope: str = ""  # the op's op_name ('jit(f)/lpa.gather/gather'), or ''


def instruction(name: str) -> str:
    """``%fusion.2 = f32[...] fusion(...)`` -> ``fusion.2``."""
    m = _INSTRUCTION.match(name)
    return m.group(1) if m else name


def op_names_of(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` of a compiled module's text."""
    return dict(m.groups() for m in map(_OP_NAME.match,
                                        hlo_text.splitlines()) if m)


def with_scopes(events: list, op_names: dict) -> list[ScopedEvent]:
    """``events`` (of ``trace.extract``) with each device op of a module
    named in ``op_names`` (module name -> :func:`op_names_of`) given its
    ``op_name``; the module is the one whose event covers the op's start
    on the op's plane."""
    modules = collections.defaultdict(list)
    for e in events:
        if e.line == "XLA Modules":
            modules[e.plane].append((e.start_ns, e.end_ns,
                                     tr.module_name(e.name)))
    for v in modules.values():
        v.sort()
    out = []
    for e in events:
        scope = ""
        if e.line == "XLA Ops":
            mods = modules.get(e.plane, [])
            i = bisect.bisect_right(mods, (e.start_ns, float("inf"))) - 1
            if i >= 0 and e.start_ns < mods[i][1]:
                scope = op_names.get(mods[i][2], {}).get(
                    instruction(e.name), "")
        out.append(ScopedEvent(*dataclasses.astuple(e)[:5], scope))
    return out


def save(events: list[ScopedEvent], path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load(path: str) -> list[ScopedEvent]:
    with gzip.open(path, "rt") as f:
        return [ScopedEvent(*row) for row in json.load(f)]


def scopes_of(op_name: str) -> list[str]:
    """The ``lpa.*`` components of an ``op_name`` (merged ops join theirs
    with ``;``), outermost first."""
    return [c for part in op_name.split(";") for c in part.split("/")
            if c.startswith(PREFIX)]


def in_scope(op_name: str, scope: str) -> bool:
    """Whether ``op_name`` lies in ``scope`` (``lpa.fold`` holds
    ``lpa.fold.r0``)."""
    return any(c == scope or c.startswith(scope + ".")
               for c in scopes_of(op_name))


def scope_s(ops, scope: str) -> float:
    """Device seconds of the ops in ``scope``, averaged over the device
    planes among ``ops``."""
    devices = {e.plane for e in ops if tr.is_device_plane(e.plane)}
    s = sum(e.dur_ns for e in ops if in_scope(getattr(e, "scope", ""),
                                              scope))
    return s * 1e-9 / max(len(devices), 1)


def lpa_spans(host, prefix: str = PREFIX) -> list:
    """The program's host spans among ``host`` events."""
    return [h for h in host if h.name.startswith(prefix)]


def idle_gaps(ops, t0: float, t1: float) -> list:
    """[start, end) intervals of ``[t0, t1)`` in which no op runs on the
    device (one device)."""
    gaps, cur = [], t0
    for s, e in sorted((max(o.start_ns, t0), min(o.end_ns, t1))
                       for o in ops):
        if s > cur:
            gaps.append((cur, min(s, t1)))
        cur = max(cur, e)
    if cur < t1:
        gaps.append((cur, t1))
    return [(s, e) for s, e in gaps if e > s]


def idle_by_span(summary: tr.Summary) -> dict:
    """Device-idle seconds in the summary's window by the innermost
    ``lpa.*`` host span covering each idle instant (``""`` where no such
    span covers it), averaged over the devices traced."""
    spans = lpa_spans(summary.host)
    edges = sorted({summary.t0, summary.t1}
                   | {max(min(x, summary.t1), summary.t0)
                      for h in spans for x in (h.start_ns, h.end_ns)})
    out = collections.Counter()
    for dev in summary.devices:
        ops = [o for o in summary.ops if o.plane == dev]
        for g0, g1 in idle_gaps(ops, summary.t0, summary.t1):
            lo, hi = bisect.bisect_right(edges, g0), bisect.bisect_left(
                edges, g1)
            cuts = [g0] + edges[lo:hi] + [g1]
            for a, b in zip(cuts, cuts[1:]):
                mid = (a + b) / 2
                cover = [h for h in spans if h.start_ns <= mid < h.end_ns]
                name = max(cover, key=lambda h: (h.start_ns, -h.dur_ns)
                           ).name if cover else ""
                out[name] += (b - a) * 1e-9
    n = max(len(summary.devices), 1)
    return {k: v / n for k, v in out.items()}


def idle_in(summary: tr.Summary, *prefixes: str) -> float | None:
    """Idle seconds charged to spans whose name starts with any of
    ``prefixes``; None when the trace has no ``lpa.*`` span."""
    if not lpa_spans(summary.host):
        return None
    return sum(v for k, v in idle_by_span(summary).items()
               if k and k.startswith(prefixes))


def sync_count(summary: tr.Summary) -> int:
    """Host reads in the window: its ``lpa.sync.*`` spans."""
    return len(lpa_spans(summary.host, PREFIX + "sync."))
