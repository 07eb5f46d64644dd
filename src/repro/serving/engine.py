"""Serving engine: batched LM generation over the cached decode step, and
the FM-index query server — the two production serve paths.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..configs.base import ArchConfig
from ..kernels.rank_select import rows_placed
from ..models import transformer as tf
from ..sharding import MeshContext


@dataclasses.dataclass
class GenerateResult:
    tokens: np.ndarray       # (B, prompt+gen)
    tokens_per_s: float


def generate(
    params,
    cfg: ArchConfig,
    ctx: MeshContext,
    prompts: np.ndarray,     # (B, prompt_len) int32
    max_new_tokens: int,
    *,
    dtype=jnp.float32,
    cache_dtype=None,
    sample: Callable | None = None,   # logits (B, V) -> token (B,)
) -> GenerateResult:
    """Greedy (or custom-sampled) batched generation with a donated cache.

    ``prompts`` int32[B, prompt_len]; returns all B sequences extended to
    ``prompt_len + max_new_tokens`` (int32) plus tokens/s.  The decode step
    is one jit'd program reused every position; ``sample`` maps logits
    float[B, V] -> token int[B] (None = argmax)."""
    B, prompt_len = prompts.shape
    total = prompt_len + max_new_tokens
    step = jax.jit(
        lambda p, c, t, pos: tf.decode_step(p, c, t, pos, cfg, ctx),
        donate_argnums=(1,),
    )
    cache = tf.init_cache(cfg, B, total, cache_dtype or dtype)
    out = np.zeros((B, total), np.int32)
    out[:, :prompt_len] = prompts
    tok = jnp.asarray(prompts[:, :1])
    t0 = time.perf_counter()
    for pos in range(total - 1):
        logits, cache = step(params, cache, tok, jnp.int32(pos))
        if pos + 1 < prompt_len:
            tok = jnp.asarray(prompts[:, pos + 1 : pos + 2])
        else:
            nxt = (
                jnp.argmax(logits, axis=-1) if sample is None else sample(logits)
            )
            tok = nxt[:, None].astype(jnp.int32)
            out[:, pos + 1] = np.asarray(tok)[:, 0]
    dt = time.perf_counter() - t0
    return GenerateResult(out, B * (total - 1) / dt)


@dataclasses.dataclass
class FMQueryResult:
    """One answered request.  ``positions`` is None for count requests."""

    kind: str                       # "count" | "locate"
    count: int
    positions: np.ndarray | None = None


def _fetch(*arrays) -> tuple[np.ndarray, ...]:
    """Host copies of an index call's results; where the index returned
    device arrays, the wait for and copy of them is an ``fm.fetch`` span."""
    if not any(isinstance(a, jax.Array) for a in arrays):
        return tuple(np.asarray(a) for a in arrays)
    with TraceAnnotation("fm.fetch"):
        return tuple(np.asarray(a) for a in arrays)


@dataclasses.dataclass
class FMServerStats:
    queries: int = 0
    batches: int = 0
    seconds: float = 0.0

    @property
    def qps(self) -> float:
        return self.queries / self.seconds if self.seconds else 0.0


class FMQueryServer:
    """Micro-batching FM-index query server over a built SequenceIndex.

    Mixed count/locate requests accumulate via ``submit`` and are answered
    by ``flush``: requests are grouped by (kind, length bucket), each group
    is PAD-padded to a fixed (batch, length) shape, and one jit'd index call
    dispatches per bucket — steady-state serving therefore reuses a small
    set of compiled programs no matter what request shapes arrive (the same
    playbook as fixed-shape LM decode buckets).  ``stats`` accumulates a
    tokens/s-style throughput report across flushes.

    Profiler spans: ``fm.flush`` around each flush (``requests``,
    ``groups``); inside it ``fm.dispatch`` around each padded index call
    (``kind``, ``length``, ``rows`` = padded batch, ``used`` = real
    requests, ``placed`` = 1 when the packed row tables the index reads
    are stored in the rank kernel's layout), and ``fm.fetch`` around the
    wait for and copy of results the index returns on the device.
    """

    def __init__(self, index, *, length_buckets=(8, 16, 32, 64),
                 max_batch: int = 256, locate_k: int = 16,
                 completed_cap: int = 1 << 16):
        self.index = index
        self.length_buckets = tuple(sorted(length_buckets))
        self.max_batch = max_batch
        self.locate_k = locate_k
        self._queue: list[tuple[int, str, np.ndarray, int]] = []
        self._next_ticket = 0
        # answered requests retained across flushes — so a convenience
        # wrapper flushing the queue never strands an earlier submit()'s
        # result.  Bounded: beyond ``completed_cap`` the oldest tickets
        # evict (dict preserves insertion = ticket order), so a long-running
        # server (e.g. behind the async frontend, which consumes results
        # from flush()'s return value) holds O(cap) results, not O(lifetime)
        self.completed: dict[int, FMQueryResult] = {}
        self.completed_cap = completed_cap
        self.stats = FMServerStats()
        self._placed_for: tuple = ()  # the tables _tables_placed last read
        self._placed = 0

    @classmethod
    def from_config(cls, index, cfg) -> "FMQueryServer":
        """Build from a BWTIndexConfig's serving knobs."""
        return cls(index, length_buckets=cfg.serve_length_buckets,
                   max_batch=cfg.serve_max_batch, locate_k=cfg.locate_k)

    def _bucket_len(self, m: int) -> int:
        for b in self.length_buckets:
            if m <= b:
                return b
        b = self.length_buckets[-1]
        while b < m:  # oversize queries: next power-of-two bucket
            b *= 2
        return b

    def _tables_placed(self) -> int:
        """1 when the index's queries read packed row tables and every one
        is in the rank kernel's layout, else 0; read from the tables'
        formats once per set of table objects."""
        tables = self.index.packed_row_tables()
        if (len(tables) != len(self._placed_for)
                or any(a is not b for a, b in zip(tables, self._placed_for))):
            placed = bool(tables) and all(rows_placed(t) for t in tables)
            self._placed_for, self._placed = tables, int(placed)
        return self._placed

    def _bucket_batch(self, b: int) -> int:
        out = 1
        while out < b:
            out *= 2
        return min(out, self.max_batch)  # the configured cap wins over pow2

    def submit(self, pattern: np.ndarray, kind: str = "count",
               k: int | None = None) -> int:
        """Enqueue one query; returns its ticket (int, dense, per-server).

        ``pattern`` is a 1-D int sequence over the index alphabet (values
        in [1, sigma); no PAD — padding happens at flush when the bucket
        shape is known).  ``k`` overrides the server's locate_k for this
        request only."""
        if kind not in ("count", "locate"):
            raise ValueError(f"unknown query kind {kind!r}")
        if kind == "locate" and k is not None and k < 1:
            raise ValueError(f"locate needs k >= 1, got {k}")
        t = self._next_ticket
        self._next_ticket += 1
        self._queue.append(
            (t, kind, np.asarray(pattern, np.int32),
             self.locate_k if k is None else k)
        )
        return t

    def flush(self) -> dict[int, FMQueryResult]:
        """Answer every queued request; returns {ticket: result} for this
        flush (and records them in ``self.completed``).

        Requests group into fixed (kind, pow2-batch, length-bucket) shapes,
        PAD-padded, one ``index.count``/``index.locate`` dispatch per group
        — so steady state reuses a small set of jit programs.  Works over
        any index exposing that interface (``SequenceIndex``, a restored
        checkpoint, or a ``SegmentedIndex``)."""
        from ..core.fm_index import PAD

        queue, self._queue = self._queue, []
        results: dict[int, FMQueryResult] = {}
        groups: dict[tuple[str, int, int], list[tuple[int, np.ndarray]]] = {}
        with TraceAnnotation("fm.flush") as span:
            for t, kind, pat, k in queue:
                key = (kind, self._bucket_len(len(pat)),
                       k if kind == "locate" else 0)
                groups.setdefault(key, []).append((t, pat))
            span.set_metadata(requests=len(queue), groups=len(groups))
            t0 = time.perf_counter()
            for (kind, L, k), items in sorted(groups.items()):
                for lo in range(0, len(items), self.max_batch):
                    chunk = items[lo : lo + self.max_batch]
                    B = self._bucket_batch(len(chunk))
                    with TraceAnnotation("fm.dispatch", kind=kind, length=L,
                                         rows=B, used=len(chunk),
                                         placed=self._tables_placed()):
                        pats = np.full((B, L), PAD, np.int32)
                        for i, (_, pat) in enumerate(chunk):
                            pats[i, : len(pat)] = pat
                        if kind == "count":
                            (counts,) = _fetch(self.index.count(pats))
                        else:
                            pos, counts = _fetch(*self.index.locate(pats, k))
                    if kind == "count":
                        for i, (t, _) in enumerate(chunk):
                            results[t] = FMQueryResult("count", int(counts[i]))
                    else:
                        for i, (t, _) in enumerate(chunk):
                            c = int(counts[i])
                            results[t] = FMQueryResult(
                                "locate", c, pos[i, :c].copy()
                            )
                    self.stats.batches += 1
            self.stats.seconds += time.perf_counter() - t0
        self.stats.queries += len(queue)
        self.completed.update(results)
        while len(self.completed) > self.completed_cap:
            self.completed.pop(next(iter(self.completed)))
        return results

    def count(self, queries: list[np.ndarray]) -> np.ndarray:
        """Batched exact-match counts for raw variable-length queries
        (list of 1-D int sequences) -> int64[len(queries)].

        Flushes the whole queue; results for previously submit()ed tickets
        stay retrievable via ``self.completed``."""
        tickets = [self.submit(q, "count") for q in queries]
        res = self.flush()
        return np.array([res[t].count for t in tickets], np.int64)

    def locate(self, queries: list[np.ndarray], k: int | None = None):
        """First-k occurrence positions per query: list of 1-D int
        sequences -> list of int arrays (ascending positions, length =
        min(#occurrences, k)).  ``k`` applies to these queries only
        (default: the server's locate_k)."""
        tickets = [self.submit(q, "locate", k=k) for q in queries]
        res = self.flush()
        return [res[t].positions for t in tickets]

    def throughput_report(self) -> str:
        s = self.stats
        return (
            f"fm-server: {s.queries} queries in {s.batches} batches, "
            f"{s.seconds * 1e3:.1f}ms -> {s.qps:.0f} queries/s"
        )
