"""Serving launcher: build (or restore) a (distributed) FM index over a
corpus and serve batched count queries; optionally checkpoint the built
index so later launches skip construction entirely.

    # build, checkpoint, serve
    python -m repro.launch.serve --kind dna --n 65536 --ckpt-dir /tmp/idx

    # restore the checkpoint (no build) and serve immediately
    python -m repro.launch.serve --kind dna --n 65536 --ckpt-dir /tmp/idx \
        --restore --batches 10

    # async frontend: admission-controlled queue, per-bucket p50/p99 SLOs
    python -m repro.launch.serve --kind dna --n 65536 --serve-async \
        --queue-depth 4096 --max-wait-ms 2 --slo-p99-ms 50

    # segmented catalog: build + save, then restore and APPEND new text
    # (BWT-merge compaction keeps the catalog small, no rebuild)
    python -m repro.launch.serve --kind dna --n 65536 --segments 2 \
        --ckpt-dir /tmp/cat
    python -m repro.launch.serve --ckpt-dir /tmp/cat --restore \
        --append new_tokens.npy --serve-async
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np
import jax


def main(argv=None):
    from ..configs.bwt_index import CONFIG as icfg
    from . import compile_cache

    ap = argparse.ArgumentParser()
    ap.add_argument("--kind", default="dna")
    ap.add_argument("--chips", type=int, default=1,
                    help="devices to shard the build and queries over (a "
                         "1-D 'parts' mesh over the first N devices); 1 = "
                         "the single-device index")
    ap.add_argument("--n", type=int, default=1 << 16)
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--batches", type=int, default=10)
    ap.add_argument("--pattern-len", type=int, default=16)
    ap.add_argument("--engine", default="bitonic")
    ap.add_argument("--ckpt-dir", default=icfg.ckpt_dir,
                    help="checkpoint the built index here (index_io format)")
    ap.add_argument("--ckpt-keep", type=int, default=icfg.ckpt_keep,
                    help="checkpoint steps to retain under --ckpt-dir")
    ap.add_argument("--restore", action="store_true",
                    help="restore from --ckpt-dir instead of building")
    ap.add_argument("--segments", type=int, default=0,
                    help="build a segmented catalog of this many segments "
                         "(0 = monolithic index); saved under --ckpt-dir "
                         "as a SegmentedIndex catalog")
    ap.add_argument("--append", action="append", default=[],
                    metavar="TOKENS_FILE",
                    help="append tokens (.npy, or .npz with a 'tokens' "
                         "array) to the restored/built segmented catalog; "
                         "repeatable.  Triggers the background BWT-merge "
                         "compaction policy, and re-saves to --ckpt-dir")
    ap.add_argument("--serve-async", action="store_true",
                    help="serve through the admission-controlled async "
                         "frontend (per-request submits, SLO metrics)")
    ap.add_argument("--queue-depth", type=int, default=icfg.serve_queue_depth,
                    help="admission bound: submits beyond this shed")
    ap.add_argument("--max-wait-ms", type=float,
                    default=icfg.serve_max_wait_ms,
                    help="flush coalescing window for the async frontend")
    ap.add_argument("--slo-p99-ms", type=float, default=icfg.serve_slo_p99_ms,
                    help="per-bucket p99 latency target for count queries")
    ap.add_argument("--slo-p99-ms-locate", type=float,
                    default=icfg.serve_slo_p99_ms_locate,
                    help="per-bucket p99 latency target for locate queries")
    ap.add_argument("--locate-frac", type=float, default=0.2,
                    help="fraction of async requests issued as locate")
    ap.add_argument("--fault-schedule", default=None, metavar="SPEC",
                    help="arm deterministic fault injection for this run: "
                         "comma-separated failpoint triggers like "
                         "'io.write:0,merge.mid:1' (repro.testing."
                         "faultinject).  The run then exercises the "
                         "recovery paths instead of the happy path; a "
                         "fault report prints on exit")
    args = ap.parse_args(argv)
    compile_cache.enable()
    if not 1 <= args.chips <= len(jax.devices()):
        ap.error(f"--chips {args.chips}: {len(jax.devices())} devices "
                 "visible")
    if args.segments > args.n:
        ap.error(f"--segments {args.segments} exceeds --n {args.n} "
                 "(every segment needs at least one token)")

    from ..testing import faultinject

    if args.fault_schedule:
        faultinject.arm(faultinject.FaultSchedule.parse(args.fault_schedule))
        print(f"fault schedule armed: {args.fault_schedule}")

    from ..core.dist_suffix_array import DistSAConfig
    from ..core.fm_index import PAD
    from ..core.index_io import (
        describe_index,
        latest_index_step,
        restore_index,
        save_index,
    )
    from ..core.pipeline import build_index
    from ..core.segments import SegmentedIndex
    from ..data.corpus import corpus

    mesh = None
    if args.chips > 1:
        mesh = jax.make_mesh((args.chips,), ("parts",),
                             devices=jax.devices()[: args.chips])

    def load_tokens(path):
        if path.endswith(".npz"):
            with np.load(path) as z:
                return np.asarray(z["tokens"], np.int32)
        return np.asarray(np.load(path), np.int32)

    appended = [load_tokens(p) for p in args.append]
    catalog_json = (os.path.join(args.ckpt_dir, "catalog.json")
                    if args.ckpt_dir else None)

    if args.restore:
        if not args.ckpt_dir:
            ap.error("--restore requires --ckpt-dir")
        t0 = time.time()
        if catalog_json and os.path.exists(catalog_json):
            index = SegmentedIndex.load(args.ckpt_dir)
            if index.degraded:
                for q in index.quarantined:
                    print(f"WARNING: segment {q['seg_id']} quarantined "
                          f"({q['reason']}); serving degraded")
            if not index.segments:
                ap.error(f"catalog under {args.ckpt_dir} has no healthy "
                         "segments left to serve")
            toks = np.concatenate([s.tokens for s in index.segments])
            args.n = len(toks)
            print(
                f"restored segmented catalog ({len(index.segments)} "
                f"segments, {index.total_tokens} tokens, "
                f"sigma={index.sigma}) in {time.time() - t0:.1f}s"
            )
        else:
            info = describe_index(args.ckpt_dir)
            # query patterns must be sampled from the corpus the index was
            # actually built over — the manifest knows its raw length
            if info.text_length - 1 != args.n:
                print(
                    f"--n {args.n} != checkpointed corpus size "
                    f"{info.text_length - 1}; using the checkpoint's size"
                )
                args.n = info.text_length - 1
            toks = corpus(args.kind, args.n)
            index = restore_index(args.ckpt_dir, mesh)
            print(
                f"restored {info.kind} index (n={info.length}, "
                f"sigma={info.sigma}, bits={info.bits}) "
                f"in {time.time() - t0:.1f}s"
            )
    elif args.segments > 0:
        toks = corpus(args.kind, args.n)
        t0 = time.time()
        index = SegmentedIndex.from_config(int(toks.max()) + 1, icfg)
        for chunk in np.array_split(toks, args.segments):
            index.append(chunk)
        print(
            f"segmented catalog built over {len(toks)} tokens "
            f"({args.segments} segments) in {time.time() - t0:.1f}s"
        )
    else:
        toks = corpus(args.kind, args.n)
        t0 = time.time()
        index = build_index(toks, mesh,
                            sa_config=DistSAConfig(engine=args.engine))
        print(f"index built over {len(toks)} tokens in {time.time() - t0:.1f}s")
        if args.ckpt_dir:
            t0 = time.time()
            latest = latest_index_step(args.ckpt_dir)
            step = save_index(args.ckpt_dir, index,
                              step=0 if latest is None else latest + 1,
                              keep=args.ckpt_keep)
            print(
                f"checkpointed to {args.ckpt_dir} step {step} "
                f"in {time.time() - t0:.1f}s"
            )

    segmented = isinstance(index, SegmentedIndex)
    if appended and not segmented:
        ap.error("--append requires a segmented catalog "
                 "(--segments N, or --restore of one)")
    if appended and not args.serve_async:
        # synchronous appends; the async path routes them through the
        # frontend's control queue instead (compaction between flushes)
        for extra in appended:
            index.append(extra)
            merges = index.maybe_compact()
            print(f"appended {len(extra)} tokens "
                  f"({merges} merge compactions, "
                  f"{len(index.segments)} segments)")
    if segmented and args.ckpt_dir and not args.serve_async:
        index.save(args.ckpt_dir)
        print(f"segmented catalog saved to {args.ckpt_dir}")

    # sample query patterns from every text source, so --append serving
    # (sync and async alike) exercises old and new segments
    sources = [toks] + appended
    rng = np.random.default_rng(0)

    def sample(active_sources):
        src = active_sources[int(rng.integers(len(active_sources)))]
        hi = min(args.pattern_len, len(src) - 1)
        L = int(rng.integers(3, hi)) if hi > 3 else max(1, hi)
        st = int(rng.integers(0, max(1, len(src) - L)))
        return src[st : st + L]

    if args.serve_async:
        import json

        from ..serving.engine import FMQueryServer
        from ..serving.frontend import AsyncQueryFrontend, Rejected

        server = FMQueryServer.from_config(index, icfg)
        can_locate = (getattr(index, "sa_sample_rate", 0)
                      or getattr(getattr(index, "fm", None),
                                 "sa_sample_rate", 0)) != 0

        with AsyncQueryFrontend(
            server, max_queue=args.queue_depth, max_wait_ms=args.max_wait_ms,
            slo_p99_ms={"count": args.slo_p99_ms,
                        "locate": args.slo_p99_ms_locate},
        ) as fe:
            futs = []
            total = args.batches * args.batch
            for _ in range(total // 2 if appended else total):
                kind = ("locate" if can_locate
                        and rng.random() < args.locate_frac else "count")
                futs.append(fe.submit(sample([toks]), kind))
            for extra in appended:
                # live growth between flushes: append + merge compaction
                # on the worker thread, queries keep flowing
                info = fe.append(extra).result()
                print(f"async-appended {info['appended']} tokens "
                      f"({info['merges']} merge compactions, "
                      f"{info['segments']} segments)")
            for _ in range(total - len(futs)):
                kind = ("locate" if can_locate
                        and rng.random() < args.locate_frac else "count")
                futs.append(fe.submit(sample(sources), kind))
            hits = shed = 0
            errors = []
            for f in futs:
                try:
                    r = f.result()
                except Exception as e:  # noqa: BLE001 — counted, fails run
                    errors.append(repr(e))
                    continue
                if isinstance(r, Rejected):
                    shed += 1
                else:
                    hits += r.count
            m = fe.metrics()
        if segmented and args.ckpt_dir:
            index.save(args.ckpt_dir)
            print(f"segmented catalog saved to {args.ckpt_dir}")
        print(json.dumps(m, indent=2))
        print(
            f"async-serve: {m['completed']} answered "
            f"({shed} shed) at {m['qps']:.0f} qps, total_hits={hits}"
        )
        if faultinject.active() is not None:
            print(f"fault report: {faultinject.active().report()}")
            return
        # with no faults injected, a recovery path taken is a failed run:
        # the frontend keeps serving through it, the exit code must not
        failed = {"compaction quarantined": m["quarantined_segments"],
                  "worker restarts": m["worker_restarts"],
                  "failed requests": len(errors)}
        if any(failed.values()):
            for e in errors[:5]:
                print(f"request failed: {e}")
            raise SystemExit(f"serve failed: {failed}")
        return

    lats = []
    total = 0
    for _ in range(args.batches):
        pats = np.full((args.batch, args.pattern_len), PAD, np.int32)
        for i in range(args.batch):
            p = sample(sources)
            pats[i, : len(p)] = p
        t0 = time.perf_counter()
        counts = np.asarray(index.count(pats))
        lats.append(time.perf_counter() - t0)
        total += int(counts.sum())
    lats.sort()
    print(
        f"{args.batches} batches of {args.batch}: "
        f"p50={lats[len(lats) // 2] * 1e3:.1f}ms "
        f"p99={lats[-1] * 1e3:.1f}ms  total_hits={total}"
    )
    if faultinject.active() is not None:
        print(f"fault report: {faultinject.active().report()}")


if __name__ == "__main__":
    main()
