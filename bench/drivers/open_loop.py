"""Open-loop query window: requests arrive on a schedule fixed by the seed,
whether or not the server keeps up.

Set-up builds the configuration's text into a one-segment
``SegmentedIndex`` (as ``launch/serve.py`` builds it), puts the program's
``FMQueryServer`` behind its ``AsyncQueryFrontend`` and warms every
(kind, length bucket, batch) program the traffic can reach.  The window
submits each request at its due time from one generator thread; latency
runs from the due time to the future's completion, so a stall delays
every request due behind it.  Counts and located positions of a sample of
the window's requests, drawn from the seed, are checked against the plain
reference once the window has closed.
"""

from __future__ import annotations

import dataclasses
import faulthandler
import functools
import gc
import sys
import threading
import time

import numpy as np

import faults
import reference
from generators import texts

WAIT_AFTER_CLOSE_S = 60.0   # a late answer is late, not missing
STALL_DUMP_S = 1.0          # generator stopped this long: dump the stacks
STALL_REARM_S = 0.1


def bucket_len(length_buckets, m: int) -> int:
    """The server's padded length for a pattern of ``m`` symbols."""
    for b in sorted(length_buckets):
        if m <= b:
            return b
    b = max(length_buckets)
    while b < m:
        b *= 2
    return b


def pow2_batches(max_batch: int) -> list[int]:
    out, b = [], 1
    while b < max_batch:
        out.append(b)
        b *= 2
    return out + [max_batch]


class TimedServer:
    """The program's ``FMQueryServer`` with the benchmark's span around each
    flush: its host-clock start and end."""

    def __init__(self, server, tracer):
        self._server = server
        self._tracer = tracer
        self.flushes: list[tuple[float, float]] = []

    def __getattr__(self, name):
        return getattr(self._server, name)

    def submit(self, pattern, kind: str = "count", k: int | None = None):
        return self._server.submit(pattern, kind, k=k)

    def flush(self):
        t0 = time.perf_counter()
        with self._tracer.span("bench.flush"):
            out = self._server.flush()
        self.flushes.append((t0, time.perf_counter()))
        return out


@dataclasses.dataclass
class Schedule:
    due: np.ndarray          # seconds after the window opens, ascending
    kinds: list              # "count" | "locate"
    patterns: list           # int32 arrays
    k: int


def make_schedule(ctx, text: np.ndarray, rate: float, seconds: float
                  ) -> Schedule:
    """Exactly round(rate * seconds) requests, their due times uniform over
    the window (a Poisson stream conditioned on its count), with exact kind
    shares and lengths spread evenly over the range, all in an order drawn
    from the seed; each pattern cut from the text at a seeded position,
    with per-symbol substitutions among the real non-separator symbols."""
    tr = ctx.traffic
    rng = ctx.rng(0x5EED)
    N = max(1, int(round(rate * seconds)))
    due = np.sort(rng.uniform(0.0, seconds, N))
    n_loc = int(round(tr["mix"]["locate"] * N))
    kinds = np.array(["count"] * (N - n_loc) + ["locate"] * n_loc)
    kinds = list(kinds[rng.permutation(N)])
    lo, hi = tr["pattern_len"]
    lens = lo + np.arange(N) % (hi - lo + 1)
    lens = lens[rng.permutation(N)]
    starts = rng.integers(0, len(text) - hi, N)
    rows = text[starts[:, None] + np.arange(hi)[None, :]]
    sep = int(ctx.config["sigma"]) - 1        # the record separator id
    nsym = sep - 1                            # real symbols 1..sep-1
    sub = (rng.random(rows.shape) < tr["substitution_rate"]) & (rows != sep)
    shift = rng.integers(1, nsym, rows.shape)
    rows = np.where(sub, (rows - 1 + shift) % nsym + 1, rows).astype(np.int32)
    pats = [np.ascontiguousarray(rows[i, : lens[i]]) for i in range(N)]
    return Schedule(due, kinds, pats, int(tr["locate_k"]))


@dataclasses.dataclass
class State:
    text: np.ndarray
    catalog: object = None
    server: TimedServer = None
    frontend: object = None
    schedule: Schedule = None
    t_open: float = 0.0
    submit_t: np.ndarray = None
    done_t: np.ndarray = None
    results: list = None


def build(ctx, state: State) -> None:
    """The served index and its server stack (set-up work)."""
    import jax

    from repro.core.segments import SegmentedIndex
    from repro.serving.engine import FMQueryServer
    from repro.serving.frontend import AsyncQueryFrontend

    from harness import index_config

    icfg = index_config(ctx.config)
    t0 = time.perf_counter()
    cat = SegmentedIndex.from_config(int(ctx.config["sigma"]), icfg)
    seg = cat.append(state.text)
    fm = seg.index.fm
    jax.block_until_ready(fm)
    stats = seg.index.build_stats
    print(f"open_loop: built {len(state.text)} tokens in "
          f"{time.perf_counter() - t0} s; bits={fm.bits} sigma={fm.sigma}; "
          f"build {stats.as_dict() if stats else None}", flush=True)
    server = TimedServer(FMQueryServer.from_config(cat, icfg), ctx.tracer)
    state.catalog, state.server = cat, server
    state.frontend = AsyncQueryFrontend.from_config(server, icfg)


def warm(ctx, state: State) -> int:
    """Run every program the traffic can reach once: both kinds, each
    length bucket its pattern lengths fall in, each power-of-two batch up
    to the server's cap; then a few requests through the frontend."""
    s, tr = state.server, ctx.traffic
    lo, hi = tr["pattern_len"]
    lengths = sorted({bucket_len(s.length_buckets, m)
                      for m in range(lo, hi + 1)})
    text, k = state.text, int(tr["locate_k"])
    programs = 0
    for L in lengths:
        for B in pow2_batches(s.max_batch):
            pats = np.stack([text[i : i + L] for i in range(B)])
            np.asarray(state.catalog.count(pats))
            np.asarray(state.catalog.locate(pats, k)[0])
            programs += 2
    futs = [state.frontend.submit(text[i : i + lo], kind)
            for i in range(8) for kind in ("count", "locate")]
    for f in futs:
        f.result()
    return programs


def setup(ctx) -> State:
    text = texts.generate(ctx.config, ctx.seed, ctx.n)
    state = State(text=text)
    build(ctx, state)
    programs = warm(ctx, state)
    faults.apply_serving(ctx.fault, state.server)
    rate = float(ctx.traffic["rate_qps"])
    state.schedule = make_schedule(ctx, text, rate, ctx.seconds)
    print(f"open_loop: warmed {programs} programs; {len(state.schedule.due)}"
          f" requests at {rate} queries/s over {ctx.seconds} s", flush=True)
    return state


def _stamp(done_t, i, _fut):
    done_t[i] = time.perf_counter()


def _generate(fe, sched: Schedule, t_open: float, submit_t, done_t,
              results, tracer) -> None:
    armed = 0.0
    for i in range(len(sched.due)):
        delay = t_open + sched.due[i] - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        submit_t[i] = time.perf_counter()
        if submit_t[i] - armed > STALL_REARM_S:
            # a thread of the interpreter's own, which needs no GIL, prints
            # every thread's stack if the generator stops this long
            faulthandler.dump_traceback_later(STALL_DUMP_S,
                                               file=sys.__stderr__)
            armed = submit_t[i]
        with tracer.span("bench.submit"):
            fut = fe.submit(sched.patterns[i], sched.kinds[i])
        results[i] = fut
        fut.add_done_callback(functools.partial(_stamp, done_t, i))
    faulthandler.cancel_dump_traceback_later()


def run_window(ctx, state: State, sched: Schedule) -> dict:
    """Submit the schedule, wait for every answer (at most a minute past
    the close), and reduce the client-side timings."""
    N = len(sched.due)
    submit_t = np.full(N, np.nan)
    done_t = np.full(N, np.nan)
    results = [None] * N
    t_open = time.perf_counter() + 0.05
    gen = threading.Thread(
        target=_generate, name="bench-generator",
        args=(state.frontend, sched, t_open, submit_t, done_t, results,
              ctx.tracer))
    tr = ctx.traffic
    gen.start()
    if ctx.tracer.enabled:
        start = t_open + float(tr.get("trace_start_s", 0.0))
        time.sleep(max(0.0, start - time.perf_counter()))
        ctx.tracer.start()
        stop = ctx.tracer.t0 + float(tr.get("trace_seconds", ctx.seconds))
        time.sleep(max(0.0, stop - time.perf_counter()))
        ctx.tracer.stop()
    gen.join()
    deadline = time.perf_counter() + WAIT_AFTER_CLOSE_S
    for f in results:
        try:
            f.exception(timeout=max(0.0, deadline - time.perf_counter()))
        except TimeoutError:
            break
    state.t_open, state.submit_t, state.done_t = t_open, submit_t, done_t
    state.results = results
    return reduce_window(ctx, state, sched)


def _answered(r) -> bool:
    from repro.serving.engine import FMQueryResult

    return isinstance(r, FMQueryResult)


def outcome(fut):
    """("answered", result) | ("failed", why) | ("missing", why)."""
    if fut is None or not fut.done():
        return "missing", "never answered"
    if fut.exception() is not None:
        return "missing", repr(fut.exception())
    r = fut.result()
    return ("answered", r) if _answered(r) else ("failed", r)


def reduce_window(ctx, state: State, sched: Schedule) -> dict:
    due_abs = state.t_open + sched.due
    kinds = np.array(sched.kinds)
    status = [outcome(f)[0] for f in state.results]
    ok = np.array([s == "answered" for s in status])
    lat_ms = (state.done_t - due_abs) * 1e3
    out = {}
    for kind in ("count", "locate"):
        sel = ok & (kinds == kind)
        if sel.any():
            out[f"{kind}_p99_ms"] = float(np.percentile(lat_ms[sel], 99))
            out[f"{kind}_p50_ms"] = float(np.percentile(lat_ms[sel], 50))
            out[f"{kind}_answered"] = int(sel.sum())
    if ok.any():
        span = float(np.nanmax(state.done_t[ok]) - due_abs[0])
        out["lookup_qps"] = int(ok.sum()) / span
    lag_ms = (state.submit_t - due_abs) * 1e3
    out["generator_lag_p99_ms"] = float(np.nanpercentile(lag_ms, 99))
    worst = int(np.nanargmax(lag_ms))
    out["generator_lag_max_ms"] = float(lag_ms[worst])
    out["generator_lag_max_at_s"] = float(sched.due[worst])
    out["attempted"] = len(status)
    out["failed"] = status.count("failed")
    out["missing"] = status.count("missing")
    out["flushes"] = len(state.server.flushes)
    return out


def window(ctx, state: State) -> dict:
    sched = state.schedule
    out = run_window(ctx, state, sched)
    state.frontend.stop()
    print(f"open_loop: window {ctx.seconds} s: {out}", flush=True)
    print(f"open_loop: generator lag p99 {out['generator_lag_p99_ms']} ms,"
          f" max {out['generator_lag_max_ms']} ms at "
          f"{out['generator_lag_max_at_s']} s", flush=True)
    if ctx.tracer.enabled:
        collect_spans(ctx, state, sched)
    state.window_out = out
    return out


def collect_spans(ctx, state: State, sched: Schedule) -> None:
    """Host spans of the traced interval for the per-layer readers: the
    flush time inside it, and the admission wait (flush start - due time)
    of each request answered by a flush that started in it (a request's
    flush is the last one to end before its completion)."""
    t0, t1 = ctx.tracer.t0, ctx.tracer.t1
    starts = np.array([f[0] for f in state.server.flushes])
    ends = np.array([f[1] for f in state.server.flushes])
    due_abs = state.t_open + sched.due
    answered = np.isfinite(state.done_t)
    which = np.searchsorted(ends, state.done_t[answered], side="right") - 1
    flush_start = np.where(which >= 0, starts[np.maximum(which, 0)], np.nan)
    in_trace = (flush_start >= t0) & (flush_start < t1)
    busy = np.clip(np.minimum(ends, t1) - np.maximum(starts, t0), 0, None)
    ctx.spans = [("bench.flush", a, b) for a, b in state.server.flushes
                 if b > t0 and a < t1]
    ctx.counters = {
        "admit_wait_ms": ((flush_start - due_abs[answered])[in_trace]
                          * 1e3).tolist(),
        "traced_interval_s": t1 - t0,
        "flush_seconds": float(busy.sum()),
    }


def release(ctx, state: State) -> None:
    state.frontend = None
    state.server = None
    state.catalog = None
    gc.collect()


def check(ctx, state: State):
    """Compare a seeded sample of the window's answered requests with the
    plain reference: every count exactly; a locate's count as min(count,
    k), its positions distinct true occurrences, and all of them where
    there are at most k."""
    sched = state.schedule
    rng = ctx.rng(0xC4EC)
    outs = [outcome(f) for f in state.results]
    missing = sum(1 for s, _ in outs if s == "missing")
    sample = []
    for kind, want in ctx.traffic["check_sample"].items():
        idx = [i for i, (s, _) in enumerate(outs)
               if s == "answered" and sched.kinds[i] == kind]
        take = min(int(want), len(idx))
        sample += list(rng.choice(idx, take, replace=False)) if take else []
    # nothing to compare is no evidence of correct answers
    wrong = 0 if sample else 1
    if sample:
        pats = [sched.patterns[i] for i in sample]
        t0 = time.perf_counter()
        ref = reference.ExactMatcher(state.text, pats)
        for j, i in enumerate(sample):
            want = ref.positions(j, pats[j])
            wrong += not answer_ok(outs[i][1], sched.kinds[i], want,
                                   pats[j], state.text, sched.k)
        print(f"open_loop: checked {len(sample)} answers against the "
              f"reference in {time.perf_counter() - t0} s", flush=True)
    out = state.window_out
    checks = {"wrong_answers": (wrong, 0), "unanswered": (missing, 0)}
    return checks, out["attempted"], out["failed"]


def answer_ok(res, kind: str, want: np.ndarray, pat: np.ndarray,
              text: np.ndarray, k: int) -> bool:
    if kind == "count":
        return int(res.count) == len(want)
    pos = np.sort(np.asarray(res.positions, np.int64))
    if int(res.count) != min(len(want), k) or len(pos) != res.count:
        return False
    if len(np.unique(pos)) != len(pos):
        return False
    if len(want) <= k:
        return bool(np.array_equal(pos, want))
    return bool(np.isin(pos, want).all())
