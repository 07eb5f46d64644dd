#!/usr/bin/env python3
"""Knee sweep of an open-loop cell: one set-up, then one window per
offered rate, lowest first.

    python3 bench/sweep.py --workload dna-seed-open --seed 5 --seconds 15 \
        --rates 250 500 1000 2000

Prints one JSON line per rate: the latency percentiles of each kind, the
rate answered, the generator's lag, and whether a backlog grew (the
median latency of the window's last fifth of requests over that of its
first fifth).  The highest rate at which every kind meets its p99 limit
(``serve_slo_p99_ms``, ``serve_slo_p99_ms_locate``) with no growing
backlog is the cell's R_slo; its traffic file runs at 0.8 x R_slo.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import numpy as np  # noqa: E402

import harness  # noqa: E402

GROWING = 2.0   # last-fifth median latency over first-fifth median


def backlog_ratio(state, sched) -> float:
    due = state.t_open + sched.due
    lat = state.done_t - due
    fifth = max(1, len(lat) // 5)
    first, last = np.nanmedian(lat[:fifth]), np.nanmedian(lat[-fifth:])
    return float(last / first) if first > 0 else float("inf")


def main(argv=None, require_tpu: bool = True, overrides=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(BENCH.parent / "src"))
    cell = harness.load_cell(args.workload)
    for key, extra in (overrides or {}).items():
        harness._merge(getattr(cell, key), extra)
    import jax

    dev = jax.devices()[0]
    if require_tpu and dev.platform != "tpu":
        print(f"sweep: no TPU (JAX found {dev.platform})", file=sys.stderr)
        return 1
    from repro.launch import compile_cache

    compile_cache.enable()
    ctx = harness.Ctx(cell=cell, seed=args.seed, seconds=args.seconds,
                      trace=False, tracer=harness.Tracer(False, BENCH),
                      device=dev)
    drv = cell.driver
    state = drv.setup(ctx)
    gc.collect()
    gc.freeze()
    print(f"sweep: setup {time.perf_counter() - T_PROCESS} s", flush=True)
    slo = {"count": cell.config["index"]["serve_slo_p99_ms"],
           "locate": cell.config["index"]["serve_slo_p99_ms_locate"]}
    r_slo = None
    for rate in sorted(args.rates):
        sched = drv.make_schedule(ctx, state.text, rate, args.seconds)
        out = drv.run_window(ctx, state, sched)
        out["rate_qps"] = rate
        out["backlog_ratio"] = backlog_ratio(state, sched)
        ok = (out["failed"] == 0 and out["missing"] == 0
              and out["backlog_ratio"] < GROWING
              and all(out.get(f"{k}_p99_ms", np.inf) <= v
                      for k, v in slo.items()))
        out["meets_slo"] = bool(ok)
        print(json.dumps(out), flush=True)
        if ok:
            r_slo = rate
        elif r_slo is not None:
            break            # past the knee: higher rates only queue longer
        time.sleep(1.0)
    state.frontend.stop()
    print(json.dumps({"r_slo_qps": r_slo, "rates": sorted(args.rates),
                      "device": dev.device_kind}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
