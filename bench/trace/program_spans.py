#!/usr/bin/env python3
"""The program's own spans in a traced run, and the device's time under them.

The program opens ``jax.profiler.TraceAnnotation`` spans named ``fm.<...>``
in host code: on the serving path ``fm.admit`` (arg ``seq``, the request's
admission number), ``fm.coalesce``, ``fm.batch`` (``n``, ``seq_lo``,
``seq_hi``), ``fm.flush`` (``requests``, ``groups``), ``fm.dispatch``
(``kind``, ``length``, ``rows``, ``used``) and ``fm.fetch``; on the build
path ``fm.build`` (``n``, ``sigma``) and its stages ``fm.build.prepare``,
``.init``, ``.round`` (``round``, ``h``, ``cap``, ``n_active``), ``.bwt``,
``.fm`` and ``.sa_samples`` (``rows``).  Their events land in the same
``.xplane.pb`` as the device's operations, on the same clock, one host line
per thread; their int args are the counters.

``spans_of(ctx)`` gives the spans of a traced run, nested per line, and
keeps each trace it parsed by path, so every per-layer reader of one run
parses the file once.  Run as a script on a trace directory::

    python3 bench/trace/program_spans.py .bench_trace/dna-seed-open

it prints (1) device-idle seconds by the innermost ``fm.`` span open at
each gap's midpoint, ``outside the program`` for the rest; (2) the longest
interval in which no ``fm.admit`` began, and the spans of the other
threads that covered it; (3) device-operation seconds by named scope, where
the device's operation events carry the HLO ``op_name``.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib
import re
import sys

import numpy as np

PREFIX = "fm."
OUTSIDE = "outside the program"
EDGE = "trace edge"

if __name__ == "__main__":
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))

import harness  # noqa: E402

reduce = harness.load_module(harness.BENCH / "trace" / "reduce.py",
                             "bench_trace_reduce")


@dataclasses.dataclass
class Span:
    name: str
    start: float          # seconds, trace clock
    end: float
    args: dict            # the span's counters
    line: int             # host line (thread) it was recorded on
    parent: int = -1      # index of the enclosing span on that line
    child_s: float = 0.0  # time its direct children cover

    @property
    def duration(self) -> float:
        return self.end - self.start

    @property
    def self_s(self) -> float:
        """Duration less the part of it that its children cover."""
        return self.duration - self.child_s


def from_planes(planes) -> list[Span]:
    """The ``fm.`` events of every host line of planes shaped as
    ``reduce.load_planes`` gives them, line by line in order of start,
    each linked to the span that encloses it on its own line."""
    out: list[Span] = []
    line_no = 0
    for pname, lines in planes:
        if pname.startswith("/device:") and "CPU" not in pname:
            continue
        for _, events in lines:
            rows = sorted((a, -b, name, stats or {})
                          for name, a, b, stats in events
                          if name.startswith(PREFIX))
            stack: list[int] = []
            for a, neg_b, name, stats in rows:
                while stack and out[stack[-1]].end <= a:
                    stack.pop()
                span = Span(name, a, -neg_b, dict(stats), line_no,
                            stack[-1] if stack else -1)
                if stack:
                    out[stack[-1]].child_s += span.duration
                stack.append(len(out))
                out.append(span)
            line_no += 1
    return out


_CACHE: dict = {}


def load(path) -> list[Span]:
    """The spans of one ``.xplane.pb``, parsed once per file version."""
    path = pathlib.Path(path)
    st = path.stat()
    key = (str(path), st.st_mtime_ns, st.st_size)
    if key not in _CACHE:
        _CACHE[key] = from_planes(reduce.load_planes(path))
    return _CACHE[key]


def spans_of(ctx) -> list[Span] | None:
    """The program's spans in a run's trace (None where there is none)."""
    path = reduce.find_trace(ctx.tracer.directory)
    return None if path is None else load(path)


def enclosing(spans: list[Span], i: int, name: str) -> int:
    """Index of the nearest span named ``name`` that encloses span ``i`` on
    its line, or -1."""
    j = spans[i].parent
    while j >= 0 and spans[j].name != name:
        j = spans[j].parent
    return j


def per_outer(spans: list[Span], inner: str, outer: str, value) -> list:
    """For each span named ``outer``, the sum of ``value(span)`` over the
    spans named ``inner`` inside it (0 where there are none)."""
    total = {i: 0.0 for i, s in enumerate(spans) if s.name == outer}
    for i, s in enumerate(spans):
        if s.name == inner:
            j = enclosing(spans, i, outer)
            if j >= 0:
                total[j] += value(s)
    return [total[i] for i in sorted(total)]


# -- the script's three tables ----------------------------------------------

# path components of an HLO op_name that are control flow, not scopes
_PLUMBING = re.compile(r"^(while|body|cond|closed_call|pjit|jit\(main\)|"
                       r"branch_\d+_fun|checkpoint|remat|scan|"
                       r"jvp\(.*\)|transpose\(.*\))$")
_OP_NAME_KEYS = ("tf_op", "op_name", "framework_op_name")


def recorded(spans: list[Span]) -> tuple[float, float] | None:
    """The interval in which the program's spans are complete: from the
    first to the last top-level span of the line they cover longest (the
    flush worker's, the builder's).  A span open across the trace's start
    or stop is not recorded, so what the host did before or after that
    interval cannot be told from time outside the program."""
    cover, first, last = collections.Counter(), {}, {}
    for s in spans:
        if s.parent < 0:
            cover[s.line] += s.duration
            first[s.line] = min(first.get(s.line, s.start), s.start)
            last[s.line] = max(last.get(s.line, s.end), s.end)
    if not cover:
        return None
    line = max(cover, key=cover.get)
    return first[line], last[line]


def innermost(ts, spans: list[Span]) -> list:
    """For each time, the name of the shortest span holding it, or None."""
    if not spans:
        return [None] * len(ts)
    a = np.array([s.start for s in spans])
    b = np.array([s.end for s in spans])
    out = []
    for t in np.array_split(np.asarray(ts, float), max(1, len(ts) // 256)):
        inside = (a <= t[:, None]) & (t[:, None] <= b)
        j = np.argmin(np.where(inside, b - a, np.inf), axis=1)
        out += [spans[k].name if hit else None
                for k, hit in zip(j, inside.any(axis=1))]
    return out


def idle_by_span(planes, spans: list[Span]) -> collections.Counter | None:
    """Device-idle seconds, averaged over chips, by the innermost ``fm.``
    span open at each idle gap's midpoint: ``outside the program`` where
    none is, ``trace edge`` outside the interval ``recorded`` gives.  Gaps
    are those of ``reduce`` (the complement of the union of operations in
    the trace's extent).  None without a device plane."""
    summary = reduce.from_planes(planes, 1.0, host_prefix=PREFIX)
    if summary is None:
        return None
    lo, hi = recorded(spans) or (summary.hi, summary.hi)
    out = collections.Counter()
    for pname, lines in planes:
        if not pname.startswith("/device:") or "CPU" in pname:
            continue
        ops = [(a, b) for lname, events in lines
               if lname.lower() == reduce.OPS_LINE for _, a, b, _ in events]
        if not ops:
            continue
        gap_list = reduce.gaps(ops, summary.lo, summary.hi)
        mids = [(a + b) / 2 for a, b in gap_list]
        for (a, b), mid, name in zip(gap_list, mids, innermost(mids, spans)):
            label = (EDGE if not lo <= mid <= hi else name or OUTSIDE)
            out[label] += (b - a) / summary.chips
    return out


def admission_gap(spans: list[Span]):
    """(start, end, {span name: seconds}) of the longest interval between
    two consecutive ``fm.admit`` starts inside the interval ``recorded``
    gives, with how long the spans of the other threads overlapped it;
    None with fewer than two admissions there."""
    lo, hi = recorded(spans) or (0.0, -1.0)
    admits = [s for s in spans if s.name == "fm.admit"]
    starts = sorted(s.start for s in admits if lo <= s.start <= hi)
    if len(starts) < 2:
        return None
    i = max(range(len(starts) - 1), key=lambda j: starts[j + 1] - starts[j])
    a, b = starts[i], starts[i + 1]
    lines = {s.line for s in admits}
    cover = collections.Counter()
    for s in spans:
        if s.line not in lines:
            overlap = min(b, s.end) - max(a, s.start)
            if overlap > 0:
                cover[s.name] += overlap
    return a, b, cover


def _scope(op_name: str) -> str:
    parts = op_name.split("/")[:-1]
    return "/".join(p for p in parts if not _PLUMBING.match(p)) or "(top)"


def device_op_names(path):
    """(per chip, the operation events as (op_name or None, start, end);
    the stat keys seen on them), read from the ``.xplane.pb`` itself:
    ``reduce.load_planes`` keeps no device stats."""
    from jax.profiler import ProfileData

    chips, keys = [], set()
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        rows = []
        for line in plane.lines:
            if line.name.lower() != reduce.OPS_LINE:
                continue
            for ev in line.events:
                stats = dict(ev.stats)
                keys.update(stats)
                name = next((stats[k] for k in _OP_NAME_KEYS
                             if isinstance(stats.get(k), str) and stats[k]),
                            None)
                a = ev.start_ns * 1e-9
                rows.append((name, a, a + ev.duration_ns * 1e-9))
        chips.append(rows)
    return chips, sorted(keys)


def scope_seconds(chips) -> collections.Counter:
    """Device-operation seconds by named scope over the chips' (op_name,
    start, end) rows: leaf operations only, the scope being the
    ``op_name`` less its last component and its control-flow parts."""
    by = collections.Counter()
    for rows in chips:
        if not rows:
            continue
        ev = reduce.Events.from_rows(rows)
        for i in np.flatnonzero(ev.leaves()):
            name = ev.names[ev.name_id[i]]
            if name is not None:
                by[_scope(name)] += ev.end[i] - ev.start[i]
    return by


def report(where) -> str:
    """The three tables for a trace directory (or an ``.xplane.pb``)."""
    where = pathlib.Path(where)
    path = where if where.is_file() else reduce.find_trace(where)
    if path is None:
        return f"no trace under {where}"
    planes = reduce.load_planes(path)
    spans = load(path)
    out = [f"trace {path}", f"{len(spans)} program spans: " + ", ".join(
        f"{k} {v}" for k, v in sorted(collections.Counter(
            s.name for s in spans).items()))]
    idle = idle_by_span(planes, spans)
    if idle is None:
        out.append("no device plane: no idle table")
    else:
        lo, hi = recorded(spans) or (0.0, 0.0)
        total = sum(idle.values())
        within = total - idle[EDGE]
        inside = within - idle[OUTSIDE]
        out.append(f"device idle {total} s; {within} s of it in the "
                   f"{hi - lo} s in which spans are recorded whole, "
                   f"{100 * inside / within if within else 0.0}% of that "
                   f"inside a program span:")
        out += [f"  {k}\t{v}" for k, v in idle.most_common()]
    gap = admission_gap(spans)
    if gap is None:
        out.append("fewer than two fm.admit spans: no admission gap")
    else:
        a, b, cover = gap
        t0 = min(s.start for s in spans)
        out.append(f"longest interval with no fm.admit: {b - a} s, from "
                   f"{a - t0} s after the first span; other threads' spans "
                   f"over it:")
        out += [f"  {k}\t{v}" for k, v in cover.most_common()]
    chips, keys = device_op_names(path)
    by = scope_seconds(chips)
    if not by:
        out.append("the device's operation events carry no op_name, so no "
                   f"time by named scope (their stats: {keys})")
    else:
        out.append("device-operation seconds by named scope:")
        out += [f"  {k}\t{v}" for k, v in by.most_common(25)]
    return "\n".join(out)


if __name__ == "__main__":
    for d in sys.argv[1:] or [".bench_trace"]:
        print(report(d))
