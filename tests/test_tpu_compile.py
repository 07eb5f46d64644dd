"""The main-path Pallas kernels compile for a TPU v5e at real sizes.

Interpret mode (every other kernel test) cannot see the TPU tiling rules or
the VMEM budget; the chip's own compiler can, and it runs here against a
described ``v5e:2x2`` topology without a chip attached.  Nothing executes:
each test lowers and compiles one jitted wrapper from shapes, and checks
that the program holds the Mosaic kernel (``tpu_custom_call``), not a jnp
fallback.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def topo_mesh(topo):
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(topo.devices[:4]), ("parts",))


@pytest.fixture
def no_compile_cache():
    """Programs compiled for a described chip cannot be read back from the
    persistent cache without one; keep them out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("B", [1, 1024])
def test_rank_packed_compiles(one_chip, no_compile_cache, B):
    """DNA layout of a 2^26-token index: 2^20 fused rows of sigma 7 + 8
    packed words, queried one walker (merge walk) or a serving batch."""
    q = _sds((B,), jnp.int32, one_chip)
    compiled = ops.rank_packed.lower(
        _sds((1 << 20, 15), jnp.int32, one_chip), q, q, q,
        bits=4, sigma=7, impl="pallas",
    ).compile()
    _assert_kernel(compiled)


def test_rank_unpacked_compiles(one_chip, no_compile_cache):
    """Proteins layout (sigma > 16, unpacked): 2^22 tokens in 64-symbol
    blocks."""
    q = _sds((1024,), jnp.int32, one_chip)
    compiled = ops.rank_unpacked.lower(
        _sds((1 << 16, 64), jnp.int32, one_chip), q, q, q, impl="pallas",
    ).compile()
    _assert_kernel(compiled)


def test_radix_sort_compiles(one_chip, no_compile_cache):
    """Two 32-bit key words plus a payload at n = 2^22."""
    n = 1 << 22
    k = _sds((n,), jnp.uint32, one_chip)
    compiled = ops.radix_sort.lower(
        (k, k, _sds((n,), jnp.int32, one_chip)),
        num_keys=2, key_bits=(32, 32), impl="pallas",
    ).compile()
    _assert_kernel(compiled)


def test_distributed_isa_compiles(topo_mesh, no_compile_cache, monkeypatch):
    """The distributed prefix-doubling build (samplesort engine, radix
    local sorts) over a 2x2 ``parts`` mesh: one compiled round in a
    ``while_loop`` whose traced pairing distance picks the shift's
    ppermutes.  The backend here is the CPU, so the test steers the
    kernel dispatch to the chip's defaults."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import dist_suffix_array as dsa

    monkeypatch.setattr(ops, "kernels_on_chip", lambda: True)
    cfg = dsa.DistSAConfig(engine=dsa.SAMPLESORT, local_sort="radix")
    s = _sds((1 << 16,), jnp.int32, NamedSharding(topo_mesh, P(cfg.axis)))
    compiled = dsa._isa_jit.lower(s, 7, cfg, 4, topo_mesh).compile()
    _assert_kernel(compiled)
    assert "while" in compiled.as_text()
