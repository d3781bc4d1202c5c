"""Compile the served path's programs for a v5e chip that is described, not
attached (guide `on-chip-measurement` §2.3): what the TPU compiler refuses —
an unaligned slice, too much VMEM, an unimplemented 64-bit rewrite — fails
here, on the CPU, at no chip time. Kernels and programs come from
tools/aot_compile.py, which also holds the slower ones.

ONE file, topology described inside a module fixture (never at import):
only the xdist worker that is given this file loads the TPU library. The
persistent compile cache is switched off around the compiles — an entry
written for a described device cannot be read back without a chip.
"""

import os
import sys

import jax
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "tools"))

#: one real capacity bucket of the served path
#: (spark.rapids.tpu.sql.batchRowCapacity's default)
CAP = 1 << 20


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _compile(name, one_chip):
    import aot_compile
    fn, args, *static = aot_compile.smoke_programs(CAP)[name]()
    return aot_compile.compile_one(name, fn, args, one_chip,
                                   tuple(static[0]) if static else ())


@pytest.mark.parametrize("name", ["pallas.murmur3", "pallas.string_search"])
def test_pallas_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    out = _compile(name, one_chip)
    assert out["custom_calls"] >= 1, out     # the Mosaic kernel is in there


@pytest.mark.parametrize("name", ["q1.project", "q3.join_count",
                                  "exchange.piece"])
def test_sortless_program_compiles_for_v5e(name, one_chip,
                                           no_persistent_cache):
    """The join probe is a search and gathers, an exchange's piece a slice
    of the split's permutation and gathers; a sort creeping back in
    (searchsorted's method="sort" costs ~1-2 min of compile) shows here."""
    out = _compile(name, one_chip)
    assert out["hlo_sorts"] == 0, out


@pytest.mark.parametrize("name", ["q1.agg_update", "exchange.split"])
def test_sorting_program_holds_one_sort(name, one_chip, no_persistent_cache):
    """The aggregate update and the exchange's split program order rows with
    exec/common.lex_sort_permutation: ONE two-operand sort in the whole
    program, however many key and payload columns there are — the TPU
    compiler's time follows the sorts, their operands and widths."""
    out = _compile(name, one_chip)
    assert out["hlo_sorts"] == 1, out
    assert out["temp_bytes"] < 8 << 30, out  # fits beside data on 16 GB


@pytest.mark.parametrize("name", ["dec.mul_64x64", "dec.mul_128x64",
                                  "dec.avg_half_up", "q1dec.project",
                                  "q1dec.agg_final_cut",
                                  "q1.agg_final_cut"])
def test_decimal128_kernel_compiles_for_v5e(name, one_chip,
                                            no_persistent_cache):
    """The limb kernels lean on what the chip emulates: uint64 multiplies
    of 32-bit digits, 64-bit shifts and compares, a 32-step ``fori_loop``
    a digit in the HALF_UP division. TPC-H Q1's decimal project (two limb
    multiplies) and its final merge over partials cut to their groups are
    the served path's own programs; the double Q1 merges at the same cut
    (every aggregate cuts its partials: eight of 128 rows, not four of
    2^20), which has to stay as small."""
    out = _compile(name, one_chip)
    assert out["temp_bytes"] < 1 << 30, out


def test_64bit_bitcast_is_still_unimplemented(one_chip, no_persistent_cache):
    """docs/tpu_compat.md: the x64 rewrite has no 64-bit bitcast-convert —
    why orderable_words() and the hashes split f64/i64 arithmetically. When
    this starts to compile, that code can go."""
    import jax.numpy as jnp
    x = jax.ShapeDtypeStruct((CAP,), jnp.float64, sharding=one_chip)
    with pytest.raises(Exception, match="(?i)x64|bitcast"):
        jax.jit(lambda v: jax.lax.bitcast_convert_type(v, jnp.uint64)) \
            .lower(x).compile()
