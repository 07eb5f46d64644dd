"""Distributed-sort engine ablation: bitonic merge-exchange vs sample sort,
with and without fused pair keys.

Wall time on one CPU core is meaningless for collectives, so the DERIVED
metric is per-device collective traffic (parsed from the compiled HLO of an
8-virtual-device run, the same parser the roofline uses) plus single-device
local-sort wall time as the compute proxy.

The volumes verify the DESIGN.md §4 analysis: bitonic moves
m*log2(P)*(log2(P)+1)/2 per sort vs samplesort's ~(beta+1)*m, so samplesort
wins on traffic at P >= 8 unless skew forces capacity retries.  The
``*_fused`` rows sort one packed uint32 key word + payload instead of two
int32 keys + payload (core.keypack): 2/3 the operands, 2/3 the shuffle
bytes.  Local rows compare lax.sort against the radix engine on the same
fused keys.
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

_PROBE = r"""
import os, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map
from repro.core.dist_sort import ShardInfo, bitonic_sort_sharded, samplesort_sharded
from repro.launch.roofline import collective_bytes

P_DEV = 8
M = 1 << 12
info = ShardInfo("parts", P_DEV, M)
mesh = jax.make_mesh((P_DEV,), ("parts",))

def bitonic(a, b, c):
    return bitonic_sort_sharded(info, (a, b, c), num_keys=2)

def sample(a, b, c):
    r = samplesort_sharded(info, (a, b, c), num_keys=2, capacity_factor=2.0)
    return r.operands

# fused-key variants: one uint32 key word + index payload (core.keypack
# packing for n <= 65535) instead of two int32 keys
def bitonic_fused(k, c):
    return bitonic_sort_sharded(info, (k, c), num_keys=1)

def sample_fused(k, c):
    r = samplesort_sharded(info, (k, c), num_keys=1, capacity_factor=2.0)
    return r.operands

out = {}
CASES = (
    ("bitonic", bitonic, (jnp.int32,) * 3),
    ("samplesort", sample, (jnp.int32,) * 3),
    ("bitonic_fused", bitonic_fused, (jnp.uint32, jnp.int32)),
    ("samplesort_fused", sample_fused, (jnp.uint32, jnp.int32)),
)
for name, fn, dtypes in CASES:
    f = jax.jit(shard_map(fn, mesh=mesh,
                          in_specs=(P("parts"),) * len(dtypes),
                          out_specs=(P("parts"),) * len(dtypes)))
    args = [jax.ShapeDtypeStruct((P_DEV * M,), dt,
            sharding=jax.sharding.NamedSharding(mesh, P("parts")))
            for dt in dtypes]
    compiled = f.lower(*args).compile()
    stats = collective_bytes(compiled.as_text())
    out[name] = {"bytes_per_device": stats.total_bytes,
                 "counts": stats.counts}
print(json.dumps(out))
"""

_SRC = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "src")
)


def collective_volumes():
    # resolve src relative to THIS file (not the caller's cwd) and hand it
    # to the subprocess via PYTHONPATH, so the probe imports `repro` no
    # matter where the benchmark is invoked from
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    # the probe counts HLO volume on 8 virtual CPU devices; it must never
    # reach for the accelerator, which the parent process may already hold
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = _SRC + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    proc = subprocess.run(
        [sys.executable, "-c", _PROBE], capture_output=True, text=True,
        timeout=600, env=env,
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    import json

    return json.loads(proc.stdout.strip().splitlines()[-1])


def local_sort_times(n=1 << 18, reps=3):
    """Single-device local-sort compute proxies: the seed 3-operand
    2-key sort vs the fused 1-key layouts (compare and radix engines)."""
    from repro.kernels import ops as kernel_ops

    rng = np.random.default_rng(0)
    k1 = jnp.asarray(rng.integers(0, 1 << 15, n).astype(np.int32))
    k2 = jnp.asarray(rng.integers(0, 1 << 15, n).astype(np.int32))
    fused = jnp.asarray(
        ((np.asarray(k1).astype(np.uint32) << 16)
         | np.asarray(k2).astype(np.uint32))
    )
    pay = jnp.arange(n, dtype=jnp.int32)
    cases = {
        "local_3op_compare": (
            jax.jit(lambda a, b, c: jax.lax.sort((a, b, c), num_keys=2)),
            (k1, k2, pay)),
        "local_fused_compare": (
            jax.jit(lambda k, c: jax.lax.sort((k, c), num_keys=1)),
            (fused, pay)),
        "local_fused_radix": (
            lambda k, c: kernel_ops.radix_sort(
                (k, c), num_keys=1, key_bits=(31,)),
            (fused, pay)),
    }
    out = {}
    for name, (f, args) in cases.items():
        f(*args)[0].block_until_ready()
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            f(*args)[0].block_until_ready()
            ts.append(time.perf_counter() - t0)
        out[name] = min(ts)
    return out


def main():
    from repro.launch import compile_cache

    compile_cache.enable()
    vols = collective_volumes()
    locals_ = local_sort_times()
    print("sortbench,engine,bytes_per_device,collective_ops,local_sort_us")
    for eng, d in vols.items():
        nops = sum(d["counts"].values())
        print(f"sortbench,{eng},{d['bytes_per_device']},{nops},-")
    for name, t in locals_.items():
        print(f"sortbench,{name},-,-,{t * 1e6:.0f}")


if __name__ == "__main__":
    main()
