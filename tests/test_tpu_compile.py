"""Compile the main-path Pallas kernels for a TPU v5e without a chip.

The TPU compiler is installed with jaxlib and compiles for a chip that is
described (``jax.experimental.topologies``) rather than attached.  It
refuses what interpret mode accepts — unaligned blocks, reductions the chip
has no instruction for, more fast memory than a kernel may use — so these
ahead-of-time compiles at real widths (128 lanes, K = 256) guard every
change to the kernels at no chip time.  Nothing runs: results are covered
by the interpret-mode suites and, on hardware, by ``test_tpu_hw.py``.

All of these tests stay in this one file.  The topology is described in a
module fixture (never while a module is imported), which skips where it
cannot be described; the persistent compilation cache is off around the
compiles, since an executable compiled for a described chip cannot be read
back without one.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import coder, spc
from repro.kernels import common, ops
from repro.kernels.rans_decode import (rans_decode_slab, rans_decode_step,
                                       slab_window_rows)

LANES, K = 128, 256


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def compiled_kernels():
    """Kernels lower compiled (not interpreted) while this file runs, with
    the persistent compilation cache off; in-process caches are dropped on
    both sides so no interpret-mode trace is reused and none leaks out."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(common, "interpret_mode", lambda: False)
        yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture
def aot(one_chip, compiled_kernels):
    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def tables(lead):
        return spc.TableSet(*[shape(lead + ((K + 1) if f == "cdf" else K,),
                                    jnp.uint32)
                              for f in spc.TableSet._fields])

    def compile_(fn, *args):
        compiled = jax.jit(fn).lower(*args).compile()
        assert "tpu_custom_call" in compiled.as_text()
        return compiled
    return shape, tables, compile_


@pytest.mark.parametrize("scatter", ["ring", "onehot"])
def test_chunked_encode_compiles(aot, scatter):
    shape, tables, compile_ = aot
    compile_(lambda s, t: ops.rans_encode_chunked(s, t, 1024,
                                                  scatter=scatter),
             shape((LANES, 4096), jnp.int32), tables(()))


@pytest.mark.parametrize("layout", ["static", "adaptive"])
def test_block_decode_compiles(aot, layout):
    shape, tables, compile_ = aot
    t_len, t_block = (1024, None) if layout == "static" else (256, 8)
    tbl = tables(() if layout == "static" else (t_len, LANES))
    cands = (None if layout == "static"
             else shape((t_len, LANES, 4), jnp.int32))
    cap = coder.default_cap(t_len)

    def decode(buf, start, tbl, cands):
        enc = coder.EncodedLanes(buf, start, start, start > 0)
        return ops.rans_decode(enc, t_len, tbl, candidates=cands,
                               t_block=t_block, exhausted_flags=True)
    compile_(decode, shape((LANES, cap), jnp.uint8),
             shape((LANES,), jnp.int32), tbl, cands)


def test_zero_copy_slab_decode_compiles(aot):
    shape, tables, compile_ = aot
    t_len, chunk = 4096, 1024
    n_chunks, cap = t_len // chunk, coder.default_cap(chunk)
    plane = shape((n_chunks, LANES), jnp.int32)
    tbl = tables(())
    compile_(lambda words, base, ws, wl, freq, cdf: rans_decode_slab(
        words, base, ws, wl, freq, cdf, cap=cap, t_len=t_len,
        chunk_size=chunk),
        shape((16 * slab_window_rows(cap), 128), jnp.uint32), plane, plane,
        plane, tbl.freq, tbl.cdf)


@pytest.mark.parametrize("rows", [16, 128])
def test_decode_step_compiles(aot, rows):
    """The fused serve decode's and BatchEngine's per-step kernel: per-row
    (rows, K) tables and top-4 model candidates."""
    shape, _, compile_ = aot
    cap = coder.default_cap(256)
    compile_(lambda buf, s, ptr, freq, cdf, cands: rans_decode_step(
        buf, s, ptr, freq, cdf, candidates=cands),
        shape((cap, rows), jnp.uint8), shape((rows,), jnp.uint32),
        shape((rows,), jnp.int32), shape((rows, K), jnp.uint32),
        shape((rows, K + 1), jnp.uint32), shape((rows, 4), jnp.int32))


def test_spc_quantize_compiles(aot):
    """The SPC mass-correction kernel (off the serve path, which quantizes
    in XLA) compiles too."""
    shape, _, compile_ = aot
    from repro.kernels.spc_quantize import spc_quantize
    compile_(spc_quantize, shape((8, K), jnp.float32))
