"""Reduction of a profiler trace to the numbers the per-layer metrics read.

A trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  On a TPU each
chip is a plane ``/device:TPU:<i>`` with a line of operations (``XLA
Ops``: one event per executed HLO instruction, a loop's body instructions
nested inside the loop's own event, each named by its HLO text with
shapes) and a line of whole programs (``XLA Modules``, named
``jit_<function>(<hash>)``).  The host plane holds the benchmark's own
``TraceAnnotation`` spans, whose names start with ``bench.``.  All times
here are seconds on the trace's clock.

- busy: the union of the intervals in which an operation ran, per chip,
  averaged over the chips that ran any;
- idle gaps: the complement of busy within the trace's extent, each
  labelled ``<span> after <program>``: the innermost benchmark span
  around its midpoint (or ``host: no span``) and the last program to end
  on that chip before the gap (or ``trace start``);
- time by operation, counting only leaf operations (not a loop around
  them), labelled ``<program>/<instruction> <output shape>``;
- ``call_shapes``: output and operand shapes of an instruction's HLO
  text, which the kernel byte counts read.
"""

from __future__ import annotations

import collections
import dataclasses
import pathlib
import re

import numpy as np

OPS_LINE = "xla ops"
MODULES_LINE = "xla modules"
NO_SPAN = "host: no span"
NO_PROGRAM = "trace start"
_SHAPE = re.compile(r"\b([a-z]+[0-9]*)\[([0-9,]*)\]")


def union_length(intervals) -> float:
    """Total length covered by (start, end) intervals."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def gaps(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    """The parts of [lo, hi] that no interval covers."""
    out, at = [], lo
    for a, b in sorted(intervals):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if hi > at:
        out.append((at, hi))
    return out


def _labels(ts, spans) -> list[str]:
    """For each time, the name of the shortest span holding it."""
    if not spans:
        return [NO_SPAN] * len(ts)
    a = np.asarray([s[1] for s in spans])
    b = np.asarray([s[2] for s in spans])
    width = b - a
    out = []
    for t in ts:
        inside = (a <= t) & (t <= b)
        out.append(spans[int(np.argmin(np.where(inside, width, np.inf)))][0]
                   if inside.any() else NO_SPAN)
    return out


def label_of(t: float, spans) -> str:
    return _labels([t], spans)[0]


def idle_by_label(gap_list, spans, modules=()) -> collections.Counter:
    """Idle seconds by ``<span> after <program>``."""
    out = collections.Counter()
    mids = [(a + b) / 2 for a, b in gap_list]
    ends = sorted((b, short_module(m)) for m, _, b in modules)
    end_t = [b for b, _ in ends]
    for (a, b), name in zip(gap_list, _labels(mids, spans)):
        j = int(np.searchsorted(end_t, a, side="right")) - 1
        prog = ends[j][1] if j >= 0 else NO_PROGRAM
        out[f"{name} after {prog}"] += b - a
    return out


def call_shapes(hlo: str) -> tuple[list, list]:
    """(output shapes, operand shapes) of one HLO instruction's text, each
    a (dtype, dims) pair: ``%x = s32[8,256]{..} custom-call(s32[1] %a,
    s32[64,128] %b), ...`` gives ([("s32", (8, 256))], [("s32", (1,)),
    ("s32", (64, 128))])."""
    head, _, rest = hlo.partition(" = ")
    opcode = re.search(r"\)?\s*([a-z\-]+)\(", rest)
    if opcode is None:
        return [], []
    out_txt, args_txt = rest[: opcode.start(1)], rest[opcode.end(1):]
    depth, end = 0, len(args_txt)
    for i, ch in enumerate(args_txt):
        depth += ch == "("
        depth -= ch == ")"
        if depth == 0:
            end = i
            break

    def shapes(txt):
        return [(d, tuple(int(x) for x in dims.split(",") if x))
                for d, dims in _SHAPE.findall(txt)]

    return shapes(out_txt), shapes(args_txt[:end])


def short_op(name: str) -> str:
    """``fusion.85 s32[16785408,3]`` for an op's HLO text."""
    if not name.startswith("%"):
        return name
    instr = name[1:].split(" = ", 1)[0]
    outs, _ = call_shapes(name)
    shape = ",".join(f"{d}[{','.join(map(str, s))}]" for d, s in outs[:2])
    kind = " tpu_custom_call" if "tpu_custom_call" in name else ""
    return f"{instr}{kind} {shape}".strip()


def short_module(name: str) -> str:
    return name.split("(", 1)[0]


@dataclasses.dataclass
class Events:
    """One line's events as arrays: name index, start and end (s)."""

    names: list
    name_id: np.ndarray
    start: np.ndarray
    end: np.ndarray

    @classmethod
    def from_rows(cls, rows):
        names, ids, st, en = {}, [], [], []
        for name, a, b in rows:
            ids.append(names.setdefault(name, len(names)))
            st.append(a)
            en.append(b)
        return cls(list(names), np.asarray(ids, np.int64),
                   np.asarray(st, np.float64), np.asarray(en, np.float64))

    def __len__(self):
        return len(self.start)

    def select(self, pred) -> np.ndarray:
        """Mask of the events whose name satisfies ``pred``."""
        if not len(self):
            return np.zeros(0, bool)
        keep = np.array([bool(pred(n)) for n in self.names])
        return keep[self.name_id]

    def leaves(self) -> np.ndarray:
        """Mask of the events that hold no other event (a loop's event
        holds its body's)."""
        order = np.lexsort((-self.end, self.start))
        s, e = self.start[order], self.end[order]
        holds = np.zeros(len(self), bool)
        holds[:-1] = s[1:] < e[:-1]
        out = np.empty(len(self), bool)
        out[order] = ~holds
        return out


@dataclasses.dataclass
class TraceSummary:
    window_s: float           # the traced interval, host clock
    busy_s: float             # device busy, averaged over chips
    lo: float                 # extent of the trace, trace clock
    hi: float
    ops: Events               # device operations, all chips
    modules: Events           # device programs, all chips
    host_spans: list          # (name, start, end, stats) of bench spans
    idle: collections.Counter  # idle seconds by host span label
    chips: int

    @property
    def idle_share(self) -> float:
        return max(0.0, 1.0 - self.busy_s / self.window_s)

    def op_seconds(self) -> collections.Counter:
        """Leaf operation seconds by ``<program>/<op> <shape>``."""
        leaf = self.ops.leaves()
        mod_order = np.argsort(self.modules.start)
        m_start = self.modules.start[mod_order]
        m_end = self.modules.end[mod_order]
        out = collections.Counter()
        for i in np.flatnonzero(leaf):
            a = self.ops.start[i]
            j = np.searchsorted(m_start, a, side="right") - 1
            mod = (short_module(self.modules.names[
                self.modules.name_id[mod_order[j]]])
                if j >= 0 and a <= m_end[j] else "?")
            out[f"{mod}/{short_op(self.ops.names[self.ops.name_id[i]])}"] += \
                self.ops.end[i] - a
        return out

    def breakdown(self) -> dict:
        top = self.op_seconds().most_common(10)
        return {"device_ops": [[k, v] for k, v in top],
                "idle_gaps": [[k, v] for k, v in self.idle.most_common(10)]}


def from_planes(planes, host_window_s: float,
                host_prefix: str = "bench.") -> TraceSummary | None:
    """Reduce planes of (name, [(line name, [(event name, start_s, end_s,
    stats)])]) — the shape ``load_planes`` gives and tests build by
    hand."""
    per_chip, ops_rows, mod_rows, spans = [], [], [], []
    chip_mods = []
    lo, hi = np.inf, -np.inf
    for pname, lines in planes:
        device = pname.startswith("/device:") and "CPU" not in pname
        chip, mods = [], []
        for lname, events in lines:
            for name, a, b, stats in events:
                lo, hi = min(lo, a), max(hi, b)
                if device and lname.lower() == OPS_LINE:
                    chip.append((a, b))
                    ops_rows.append((name, a, b))
                elif device and lname.lower() == MODULES_LINE:
                    mod_rows.append((name, a, b))
                    mods.append((name, a, b))
                elif not device and name.startswith(host_prefix):
                    spans.append((name, a, b, stats))
        if chip:
            per_chip.append(chip)
            chip_mods.append(mods)
    if not per_chip:
        return None
    idle = collections.Counter()
    for chip, mods in zip(per_chip, chip_mods):
        for k, v in idle_by_label(gaps(chip, lo, hi), spans, mods).items():
            idle[k] += v / len(per_chip)
    return TraceSummary(
        window_s=host_window_s,
        busy_s=float(np.mean([union_length(c) for c in per_chip])),
        lo=lo, hi=hi, ops=Events.from_rows(ops_rows),
        modules=Events.from_rows(mod_rows), host_spans=spans, idle=idle,
        chips=len(per_chip))


def find_trace(directory) -> pathlib.Path | None:
    found = sorted(pathlib.Path(directory).glob(
        "plugins/profile/*/*.xplane.pb"))
    return found[-1] if found else None


def load_planes(path) -> list:
    """Planes of an ``.xplane.pb`` as plain tuples, times in seconds;
    stats are kept for host events only."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    planes = []
    for plane in data.planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device:
                events = [(ev.name, ev.start_ns * 1e-9,
                           (ev.start_ns + ev.duration_ns) * 1e-9, None)
                          for ev in line.events]
            else:
                events = [(ev.name, ev.start_ns * 1e-9,
                           (ev.start_ns + ev.duration_ns) * 1e-9,
                           {k: v for k, v in ev.stats})
                          for ev in line.events]
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def summarize(directory, host_window, host_prefix: str = "bench."
              ) -> TraceSummary | None:
    """The summary of the trace under ``directory`` (None where there is
    no trace or no device operation in it)."""
    path = find_trace(directory)
    if path is None:
        return None
    t0, t1 = host_window
    return from_planes(load_planes(path), t1 - t0, host_prefix)
