"""Async serving frontend + FMQueryServer edge cases: empty flush,
oversize queries, admission-control shedding, drain-on-stop, per-bucket
metrics — and the stacked segment-parallel fan-out's bit-identity with the
sequential path (including across a compact() boundary)."""

import threading
import time

import numpy as np
import pytest

from repro.core.fm_index import PAD, count_naive
from repro.core.pipeline import build_index
from repro.core.segments import SegmentedIndex
from repro.serving.engine import FMQueryServer
from repro.serving.frontend import (
    AsyncQueryFrontend,
    DeadlineExceeded,
    Rejected,
)
from repro.testing import faultinject
from repro.testing.faultinject import FaultSchedule, InjectedFault

SIGMA = 5  # dna-like: tokens 1..4


@pytest.fixture(scope="module")
def built():
    rng = np.random.default_rng(7)
    toks = rng.integers(1, SIGMA, 2000).astype(np.int32)
    index = build_index(toks, sample_rate=16, sa_sample_rate=8)
    return rng, toks, index


def _server(index, **kw):
    kw.setdefault("length_buckets", (4, 8))
    kw.setdefault("max_batch", 16)
    kw.setdefault("locate_k", 4)
    return FMQueryServer(index, **kw)


class TestServerEdges:
    def test_empty_flush(self, built):
        _, _, index = built
        server = _server(index)
        assert server.flush() == {}
        assert server.stats.queries == 0 and server.stats.batches == 0

    def test_query_longer_than_any_bucket(self, built):
        """Oversize patterns escalate to the next pow2 bucket instead of
        truncating — the answer must equal the naive oracle."""
        _, toks, index = built
        server = _server(index)
        pat = toks[100:125]  # length 25 > largest bucket 8 -> bucket 32
        assert server._bucket_len(len(pat)) == 32
        got = server.count([pat])
        assert got[0] == count_naive(toks, pat)

    def test_flush_clears_queue_and_records_completed(self, built):
        _, toks, index = built
        server = _server(index)
        t = server.submit(toks[10:14])
        res = server.flush()
        assert server.flush() == {}  # queue drained by the first flush
        assert server.completed[t].count == res[t].count


class TestFrontend:
    def test_mixed_results_match_direct(self, built):
        rng, toks, index = built
        server = _server(index)
        pats, kinds = [], []
        for _ in range(40):
            L = int(rng.integers(2, 9))
            st = int(rng.integers(0, len(toks) - L))
            pats.append(toks[st : st + L])
            kinds.append("locate" if rng.random() < 0.5 else "count")
        with AsyncQueryFrontend(server, max_queue=256,
                                max_wait_ms=1.0) as fe:
            futs = [fe.submit(p, kd, k=4 if kd == "locate" else None)
                    for p, kd in zip(pats, kinds)]
            results = [f.result(timeout=120) for f in futs]
        L = max(len(p) for p in pats)
        padded = np.full((len(pats), L), PAD, np.int32)
        for i, p in enumerate(pats):
            padded[i, : len(p)] = p
        counts = np.asarray(index.count(padded))
        pos, _ = index.locate(padded, 4)
        pos = np.asarray(pos)
        for i, (res, kind) in enumerate(zip(results, kinds)):
            assert not isinstance(res, Rejected)
            assert res.kind == kind
            if kind == "count":
                assert res.count == counts[i]
            else:
                assert res.count == min(counts[i], 4)
                assert np.array_equal(
                    np.asarray(res.positions), pos[i][: res.count]
                )

    def test_queue_full_rejection(self, built):
        """Submits beyond max_queue shed immediately with a Rejected
        result; admitted requests still resolve once the worker runs."""
        _, toks, index = built
        fe = AsyncQueryFrontend(_server(index), max_queue=3,
                                autostart=False)
        admitted = [fe.submit(toks[:4]) for _ in range(3)]
        shed = fe.submit(toks[:4])
        assert isinstance(shed.result(timeout=1), Rejected)
        assert shed.result().reason == "queue_full"
        assert fe.rejected == 1 and fe.admitted == 3
        fe.stop()  # drains inline (worker never started)
        assert all(f.result(timeout=1).count >= 0 for f in admitted)
        m = fe.metrics()
        assert m["shed_frac"] == pytest.approx(0.25)
        assert m["completed"] == 3
        # compaction telemetry rides along even on a monolithic index
        # (attribute-absent fallbacks): zero fallbacks, no reason, no counts
        assert m["compact_fallbacks"] == 0
        assert m["compact_last_fallback_reason"] is None
        assert m["compact_strategy_counts"] == {}

    def test_burst_sheds_without_crashing(self, built):
        """Open-loop burst far above capacity: some requests shed, every
        admitted one answers correctly, nothing deadlocks."""
        rng, toks, index = built
        expect = {}
        with AsyncQueryFrontend(_server(index), max_queue=8,
                                max_wait_ms=0.5) as fe:
            futs = []
            for i in range(200):
                L = int(rng.integers(2, 9))
                st = int(rng.integers(0, len(toks) - L))
                expect[i] = count_naive(toks, toks[st : st + L])
                futs.append(fe.submit(toks[st : st + L]))
            results = [f.result(timeout=120) for f in futs]
        shed = sum(isinstance(r, Rejected) for r in results)
        assert shed > 0, "burst into a depth-8 queue should shed"
        for i, r in enumerate(results):
            if not isinstance(r, Rejected):
                assert r.count == expect[i]
        m = fe.metrics()
        assert m["rejected"] == shed
        assert m["admitted"] == 200 - shed == m["completed"]

    def test_metrics_buckets_have_percentiles(self, built):
        _, toks, index = built
        slo = {"count": 1e9, "locate": 1e9}
        with AsyncQueryFrontend(_server(index), max_queue=64,
                                slo_p99_ms=slo) as fe:
            futs = [fe.submit(toks[i : i + 3]) for i in range(10)]
            futs += [fe.submit(toks[i : i + 6], "locate") for i in range(5)]
            for f in futs:
                f.result(timeout=120)
            m = fe.metrics()
        assert set(m["buckets"]) == {"count/4", "locate/8"}
        b = m["buckets"]["count/4"]
        assert b["completed"] == 10
        assert 0 < b["p50_ms"] <= b["p99_ms"]
        assert b["slo_ok"] is True and b["violations"] == 0

    def test_slo_violations_counted(self, built):
        _, toks, index = built
        with AsyncQueryFrontend(_server(index), max_queue=64,
                                slo_p99_ms={"count": 1e-6}) as fe:
            fe.submit(toks[:4]).result(timeout=120)
            m = fe.metrics()
        b = m["buckets"]["count/4"]
        assert b["violations"] == 1 and b["slo_ok"] is False

    def test_worker_survives_dispatch_failure(self, built):
        """A request the server cannot answer resolves its future to the
        exception — the worker stays alive and keeps serving."""
        _, toks, index = built
        with AsyncQueryFrontend(_server(index), max_queue=16) as fe:
            bad = fe.submit(toks[:4], "locate", k=-1)  # invalid locate k
            with pytest.raises(Exception):
                bad.result(timeout=120)
            ok = fe.submit(toks[10:14])  # worker must still be alive
            assert ok.result(timeout=120).count >= 0

    def test_cancelled_future_does_not_wedge_worker(self, built):
        """A client cancelling a queued request must not kill the flush
        worker: later requests still resolve."""
        _, toks, index = built
        fe = AsyncQueryFrontend(_server(index), max_queue=16,
                                autostart=False)
        doomed = fe.submit(toks[:4])
        survivor = fe.submit(toks[10:14])
        assert doomed.cancel()  # still queued: cancellable
        fe.start()
        assert survivor.result(timeout=120).count >= 0
        with fe:  # frontend still alive and serving
            assert fe.submit(toks[:4]).result(timeout=120).count >= 0
        assert doomed.cancelled()

    def test_submit_after_stop_raises(self, built):
        _, toks, index = built
        fe = AsyncQueryFrontend(_server(index), max_queue=4)
        fe.stop()
        with pytest.raises(RuntimeError):
            fe.submit(toks[:4])

    def test_coalescing_batches_concurrent_producers(self, built):
        """Many producer threads, one flush worker: far fewer flushes than
        requests (max-wait coalescing), every result correct."""
        _, toks, index = built
        with AsyncQueryFrontend(_server(index), max_queue=1024,
                                max_wait_ms=20.0) as fe:
            futs, lock = [], threading.Lock()

            def produce():
                for _ in range(25):
                    f = fe.submit(toks[20:24])
                    with lock:
                        futs.append(f)
                    time.sleep(0.001)

            threads = [threading.Thread(target=produce) for _ in range(4)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            want = count_naive(toks, toks[20:24])
            assert all(f.result(timeout=120).count == want for f in futs)
            m = fe.metrics()
        assert m["flushes"] < m["completed"]


class TestFrontendFaults:
    """The self-healing layer: worker watchdog, per-query deadlines,
    growth-op retries, poison-op quarantine, and the close() guarantee
    that admitted futures always resolve."""

    def test_worker_crash_restarts_and_fails_only_inflight(self, built):
        """An injected ``worker.flush`` crash kills the worker thread; the
        watchdog fails that flush's futures with the crash exception,
        respawns a worker, and everything else answers exactly."""
        rng, toks, index = built
        expect = {}
        with faultinject.inject(FaultSchedule([("worker.flush", 0)])):
            with AsyncQueryFrontend(_server(index), max_queue=256,
                                    max_wait_ms=5.0) as fe:
                futs = []
                for i in range(30):
                    L = int(rng.integers(2, 9))
                    st = int(rng.integers(0, len(toks) - L))
                    expect[i] = count_naive(toks, toks[st : st + L])
                    futs.append(fe.submit(toks[st : st + L]))
                crashed = answered = 0
                for i, f in enumerate(futs):
                    try:
                        r = f.result(timeout=120)
                    except InjectedFault:
                        crashed += 1
                        continue
                    assert r.count == expect[i], i
                    answered += 1
                m = fe.metrics()
        assert crashed >= 1, "the injected crash hit no flush"
        assert answered == 30 - crashed
        assert m["worker_restarts"] == 1
        assert m["completed"] == answered

    def test_deadline_exceeded_resolves_instead_of_waiting(self, built):
        """A queued request whose deadline passes before its flush
        dispatches resolves to DeadlineExceeded — never hangs."""
        _, toks, index = built
        fe = AsyncQueryFrontend(_server(index), max_queue=16,
                                autostart=False)
        doomed = fe.submit(toks[:4], deadline_ms=0.0)
        alive = fe.submit(toks[:4], deadline_ms=60_000.0)
        time.sleep(0.005)  # let the zero deadline lapse while queued
        fe.start()
        assert isinstance(doomed.result(timeout=120), DeadlineExceeded)
        assert doomed.result().kind == "count"
        assert alive.result(timeout=120).count == count_naive(toks, toks[:4])
        fe.stop()
        m = fe.metrics()
        assert m["deadline_exceeded"] == 1 and m["completed"] == 1

    def test_negative_deadline_rejected_at_submit(self, built):
        _, toks, index = built
        fe = AsyncQueryFrontend(_server(index), max_queue=4, autostart=False)
        with pytest.raises(ValueError, match="deadline_ms"):
            fe.submit(toks[:4], deadline_ms=-1.0)
        fe.stop()

    def test_transient_compaction_fault_retried(self):
        """One injected merge crash during the growth op's compaction:
        the capped-backoff retry succeeds, nothing quarantines."""
        rng = np.random.default_rng(23)
        # force the pairwise merge: the cost model would route this tiny
        # run to the rebuild, and merge.mid only fires on the merge walk
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8,
                             segment_min_tokens=1 << 10,
                             compact_strategy="pairwise")
        seg.append(rng.integers(1, SIGMA, 300).astype(np.int32))
        new = rng.integers(1, SIGMA, 120).astype(np.int32)
        with faultinject.inject(FaultSchedule([("merge.mid", 0)])):
            with AsyncQueryFrontend(_server(seg), max_queue=16,
                                    growth_backoff_ms=1.0) as fe:
                info = fe.append(new).result(timeout=120)
                m = fe.metrics()
        assert info["merges"] == 1 and info["segments"] == 1
        assert not info["compaction_quarantined"]
        assert m["retries"] == 1 and m["quarantined_segments"] == 0
        assert not m["degraded"]

    def test_poison_compaction_quarantined_pre_compact_serves(self):
        """A compaction that fails every retry is quarantined: the append
        itself still lands, the pre-compact segments keep serving exactly,
        later appends skip compaction, and resume_compaction() recovers."""
        rng = np.random.default_rng(24)
        # forced pairwise for the same reason as above: the armed
        # merge.mid poison must sit on the executed compaction path
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8,
                             segment_min_tokens=1 << 10,
                             compact_strategy="pairwise")
        first = rng.integers(1, SIGMA, 300).astype(np.int32)
        seg.append(first)
        new = rng.integers(1, SIGMA, 120).astype(np.int32)
        # retries=3 -> exactly 4 attempts; arm a crash for each
        poison = FaultSchedule([("merge.mid", k) for k in range(4)])
        with faultinject.inject(poison):
            with AsyncQueryFrontend(_server(seg), max_queue=16,
                                    growth_backoff_ms=1.0) as fe:
                info = fe.append(new).result(timeout=120)
                assert info["appended"] == 120 and info["merges"] == 0
                assert info["compaction_quarantined"]
                assert "compaction_error" in info
                # pre-compact generation serves: both texts answer exactly
                got_old = fe.submit(first[5:11]).result(timeout=120)
                got_new = fe.submit(new[50:56]).result(timeout=120)
                assert got_old.count >= 1
                assert got_new.count >= 1
                # the quarantine sticks: this append must NOT re-attempt
                # compaction (no armed trigger left would stop it anyway)
                info2 = fe.append(
                    rng.integers(1, SIGMA, 50).astype(np.int32)
                ).result(timeout=120)
                assert info2["merges"] == 0
                assert info2["compaction_quarantined"]
                m = fe.metrics()
                assert m["quarantined_segments"] == 1
                assert m["degraded"] and m["retries"] == 3
                # operator fixed the cause: compaction resumes and merges
                # the whole backlog of small segments
                fe.resume_compaction()
                info3 = fe.append(
                    rng.integers(1, SIGMA, 50).astype(np.int32)
                ).result(timeout=120)
                assert info3["merges"] == 1 and info3["segments"] == 1
                assert not info3["compaction_quarantined"]
                assert not fe.metrics()["degraded"]
        assert len(seg.segments) == 1

    def test_submit_then_immediate_close_resolves_everything(self, built):
        """Regression: close() right after a burst of submits must resolve
        every admitted future (drain), not leave callers hanging."""
        _, toks, index = built
        want = count_naive(toks, toks[20:24])
        for trial in range(5):  # race close() against the worker repeatedly
            fe = AsyncQueryFrontend(_server(index), max_queue=256,
                                    max_wait_ms=50.0)
            futs = [fe.submit(toks[20:24]) for _ in range(8)]
            fe.close()
            for f in futs:
                assert f.result(timeout=30).count == want, trial
            with pytest.raises(RuntimeError):
                fe.submit(toks[:4])

    def test_close_after_worker_crash_still_resolves(self, built):
        """Even when the worker crashes on every flush it attempts, close()
        resolves the leftovers inline (exception or Shutdown, never a
        hang)."""
        _, toks, index = built
        with faultinject.inject(FaultSchedule([("worker.flush", 0)])):
            fe = AsyncQueryFrontend(_server(index), max_queue=64,
                                    max_wait_ms=200.0)
            futs = [fe.submit(toks[20:24]) for _ in range(6)]
            fe.close()
            outcomes = []
            for f in futs:
                try:
                    outcomes.append(f.result(timeout=30))
                except InjectedFault:
                    outcomes.append("crashed")
            assert len(outcomes) == 6  # nothing hung
        assert fe.metrics()["worker_restarts"] <= 1


class TestSegmentParallelParity:
    """The stacked fan-out must be bit-identical to the sequential loop —
    including across a compact() boundary (stacked layout rebuilt) and when
    served through the query server."""

    @pytest.fixture(scope="class")
    def seg_built(self):
        rng = np.random.default_rng(11)
        chunks = [rng.integers(1, SIGMA, n).astype(np.int32)
                  for n in (350, 120, 60, 500, 90)]
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8)
        for c in chunks:
            seg.append(c)
        full = np.concatenate(chunks)
        pats = np.full((20, 6), PAD, np.int32)
        for b in range(20):
            L = int(rng.integers(1, 7))
            st = int(rng.integers(0, len(full) - L))
            pats[b, :L] = full[st : st + L]
        return seg, pats

    def _both(self, seg, fn):
        seg.parallel, seg._stacked_cache = True, None
        par = fn()
        assert seg._stacked_cache not in (None, False), "stacked path unused"
        seg.parallel, seg._stacked_cache = False, None
        sequ = fn()
        seg.parallel = None
        return par, sequ

    def test_count_parity(self, seg_built):
        seg, pats = seg_built
        par, sequ = self._both(seg, lambda: seg.count(pats))
        assert np.array_equal(par, sequ)

    def test_locate_parity(self, seg_built):
        seg, pats = seg_built
        (pp, pc), (sp, sc) = self._both(seg, lambda: seg.locate(pats, 4))
        assert np.array_equal(pp, sp) and np.array_equal(pc, sc)

    def test_parity_across_compact_boundary(self, seg_built):
        """compact() merges runs of small segments — the rebuilt stacked
        layout must still match the sequential answers exactly."""
        seg, pats = seg_built
        before = seg.count(pats)
        assert seg.compact(min_tokens=200) >= 1
        par, sequ = self._both(seg, lambda: seg.count(pats))
        assert np.array_equal(par, sequ)
        # compaction can only reveal former cross-boundary matches
        assert (par >= before).all()
        (pp, pc), (sp, sc) = self._both(seg, lambda: seg.locate(pats, 4))
        assert np.array_equal(pp, sp) and np.array_equal(pc, sc)

    def test_served_identically_through_frontend(self, seg_built):
        seg, pats = seg_built
        seg.parallel = True
        server = _server(seg)
        with AsyncQueryFrontend(server, max_queue=64) as fe:
            futs = [fe.submit(pats[b][pats[b] != PAD]) for b in range(20)]
            got = np.array([f.result(timeout=120).count for f in futs])
        seg.parallel = None
        assert np.array_equal(got, seg.count(pats))

    def test_single_segment_auto_stays_sequential(self):
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8)
        seg.append(np.ones(50, np.int32))
        assert seg._stacked() is None  # auto: no stacking for one segment
        seg.parallel = True
        assert seg._stacked() is not None  # forced: stack of one works


class TestFrontendAppend:
    """Live index growth through the async frontend: appends apply between
    flushes on the worker thread, trigger the background merge-compaction
    policy, and queries spanning old and appended segments answer exactly."""

    def _segmented(self, rng, n=600):
        seg = SegmentedIndex(SIGMA, sample_rate=16, sa_sample_rate=8,
                             segment_min_tokens=1 << 10,
                             compact_trigger_ratio=0.5)
        seg.append(rng.integers(1, SIGMA, n).astype(np.int32))
        return seg

    def test_append_grows_index_and_compacts(self):
        rng = np.random.default_rng(17)
        seg = self._segmented(rng)
        old = seg.segments[0].tokens
        new = rng.integers(1, SIGMA, 200).astype(np.int32)
        with AsyncQueryFrontend(_server(seg), max_queue=64) as fe:
            before = fe.submit(old[5:10]).result(timeout=120)
            info = fe.append(new).result(timeout=120)
            # policy: 2/2 segments small -> merge compaction fires
            assert info["appended"] == 200 and info["merges"] == 1
            assert info["segments"] == 1
            assert info["total_tokens"] == len(old) + 200
            after_old = fe.submit(old[5:10]).result(timeout=120)
            after_new = fe.submit(new[50:55]).result(timeout=120)
            m = fe.metrics()
        assert after_old.count == before.count  # compaction is invariant
        full_docs = [old, new]
        want = sum(count_naive(d, new[50:55]) for d in full_docs)
        assert after_new.count == want and want >= 1
        assert m["appends"] == 1 and m["compactions"] == 1

    def test_append_rejected_for_monolithic_index(self, built):
        _, toks, index = built
        with AsyncQueryFrontend(_server(index), max_queue=8) as fe:
            with pytest.raises(TypeError, match="append"):
                fe.append(toks[:16])

    def test_append_error_resolves_future_and_worker_survives(self):
        rng = np.random.default_rng(18)
        seg = self._segmented(rng)
        with AsyncQueryFrontend(_server(seg), max_queue=8) as fe:
            bad = fe.append(np.array([99], np.int32))  # out of alphabet
            with pytest.raises(ValueError):
                bad.result(timeout=120)
            ok = fe.submit(seg.segments[0].tokens[:6])
            assert ok.result(timeout=120).count >= 1

    def test_serve_launcher_append_flow(self, tmp_path):
        """launch.serve end-to-end: build+save a segmented catalog, then
        restore + --append + --serve-async; the appended text must be
        queryable and the re-saved catalog must contain it."""
        from repro.launch import serve as serve_launcher

        ckpt = str(tmp_path / "cat")
        extra_path = str(tmp_path / "extra.npy")
        rng = np.random.default_rng(3)
        np.save(extra_path, rng.integers(1, 5, 512).astype(np.int32))
        serve_launcher.main([
            "--kind", "dna", "--n", "2048", "--segments", "2",
            "--batch", "4", "--batches", "2", "--ckpt-dir", ckpt,
        ])
        serve_launcher.main([
            "--restore", "--ckpt-dir", ckpt, "--append", extra_path,
            "--serve-async", "--batch", "4", "--batches", "2",
            "--queue-depth", "128",
        ])
        reloaded = SegmentedIndex.load(ckpt)
        assert reloaded.total_tokens == 2048 + 512
        extra = np.load(extra_path)
        want = count_naive(extra, extra[100:110])
        got = reloaded.count(
            np.asarray(extra[100:110], np.int32)[None, :]
        )[0]
        assert got >= want >= 1

    def test_serve_launcher_fails_on_quarantined_compaction(
            self, tmp_path, monkeypatch):
        """With no fault schedule armed, a recovery path taken while
        serving is a failed run: the frontend quarantines the poison
        compaction and keeps answering, but the launcher exits non-zero."""
        from repro.launch import serve as serve_launcher

        ckpt = str(tmp_path / "cat")
        extra_path = str(tmp_path / "extra.npy")
        np.save(extra_path,
                np.random.default_rng(4).integers(1, 5, 512).astype(np.int32))
        serve_launcher.main([
            "--kind", "dna", "--n", "2048", "--segments", "2",
            "--batch", "4", "--batches", "1", "--ckpt-dir", ckpt,
        ])

        def poison(self):
            raise RuntimeError("compaction blew up")

        monkeypatch.setattr(SegmentedIndex, "maybe_compact", poison)
        with pytest.raises(SystemExit, match="compaction quarantined"):
            serve_launcher.main([
                "--restore", "--ckpt-dir", ckpt, "--append", extra_path,
                "--serve-async", "--batch", "4", "--batches", "2",
                "--queue-depth", "128",
            ])
