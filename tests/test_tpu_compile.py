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
from jax.experimental.layout import Format
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.rank_select import ROW_LAYOUT


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


def _placed_table(one_chip, rows: int):
    """The DNA row table as ``build_fm_index`` stores it: int32[rows, 1,
    sigma 7 + 8 packed words] in the rank kernel's layout."""
    return _sds((rows, 1, 15), jnp.int32, Format(ROW_LAYOUT, one_chip))


@pytest.mark.parametrize("table", ["placed", "2d"])
@pytest.mark.parametrize("B", [1, 1024])
def test_rank_packed_compiles(one_chip, no_compile_cache, B, table):
    """DNA layout of a 2^26-token index: 2^20 fused rows of sigma 7 + 8
    packed words, queried one walker (merge walk) or a serving batch; the
    table placed as ``build_fm_index`` stores it, or 2-D as a
    ``DistFMIndex`` shard holds it."""
    q = _sds((B,), jnp.int32, one_chip)
    rows = (_placed_table(one_chip, 1 << 20) if table == "placed"
            else _sds((1 << 20, 15), jnp.int32, one_chip))
    compiled = ops.rank_packed.lower(
        rows, q, q, q, bits=4, sigma=7, impl="pallas",
    ).compile()
    _assert_kernel(compiled)


def _table_copies(text: str, rows: int) -> list[str]:
    """Instructions of a compiled program that copy a whole row table of
    ``rows`` rows, 2-D or 3-D, in any computation."""
    shapes = (f"s32[{rows},1,15]", f"s32[{rows},15]")
    return [line.strip() for line in text.splitlines()
            if any(sh in line for sh in shapes)
            and any(op in line for op in (" copy(", " copy-start(",
                                          " copy-done("))]


def test_query_programs_do_not_copy_the_table(one_chip, no_compile_cache,
                                              monkeypatch):
    """``count`` (B 512, m 64) and ``locate`` (B 128, m 32, k 16) over the
    DNA cell's index, 2^20 + 1 rows of the placed table: the compiled
    programs read the table where it lies, with no relayout copy of it in
    the entry computation or in any loop body.  The backend here is the
    CPU, so the test steers the kernel dispatch to the chip's defaults."""
    from repro.core import fm_index

    monkeypatch.setattr(ops, "kernels_on_chip", lambda: True)
    nb, sigma, r = (1 << 20) + 1, 7, 64
    n = nb * r

    def vec(size):
        return _sds((size,), jnp.int32, one_chip)

    fm = fm_index.FMIndex(
        vec(n), _sds((), jnp.int32, one_chip), vec(sigma),
        _sds((nb + 1, sigma), jnp.int32, one_chip), _placed_table(one_chip, nb),
        vec(n // 32), vec(n // 32), vec(n // 32 + 1),
        r, sigma, n - 1, 4, 32, 21,
    )
    count = fm_index.count.lower(
        fm, _sds((512, 64), jnp.int32, one_chip)).compile().as_text()
    locate = fm_index.locate.lower(
        fm, _sds((128, 32), jnp.int32, one_chip), 16).compile().as_text()
    for text in (count, locate):
        assert "tpu_custom_call" in text
        assert _table_copies(text, nb) == []


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
