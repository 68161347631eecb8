"""Container corruption fuzz tier (ISSUE 4 satellite; ``-m fuzz``).

Seeded adversarial inputs against ``bitstream.unpack`` / ``unpack_chunked``
(via :mod:`tests._prop`'s deterministic sweeps — every run and every CI
machine sees the identical case list).  Contract under corruption:

  * **never crash** — any truncation or byte flip raises ``ValueError``
    (named region/cell), never a raw ``struct.error``/numpy error, never a
    segfault-shaped surprise from a bogus allocation;
  * **never silently mis-decode what integrity covers** — with
    ``FLAG_CHUNK_CRC32`` every payload byte is inside some cell's CRC, so
    every payload flip MUST raise and MUST name the damaged (chunk, lane);
    a deliberately wrong CRC cell in the index must be caught the same way;
  * truncations at every boundary (header, length/index table, payload)
    raise errors naming the truncated region.

Checksum-less v2 and v1 payloads carry no integrity bits — flips there may
legally "succeed"; the assertion for them is only the no-crash contract
(the docs call out the tradeoff; writers default to checksums on).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from _prop import ints, sweep
from repro.core import bitstream, coder, spc

pytestmark = pytest.mark.fuzz


def _make_corpus():
    rng = np.random.default_rng(90)
    k, lanes, t, chunk = 32, 4, 48, 13
    tbl = spc.tables_from_probs(
        jnp.asarray(rng.dirichlet(np.full(k, 0.5)), jnp.float32))
    syms = jnp.asarray(rng.integers(0, k, (lanes, t)), jnp.int32)
    enc = coder.encode(syms, tbl)
    ch = coder.encode_chunked(syms, tbl, chunk)
    v1 = bitstream.pack(*map(np.asarray, enc), n_symbols=t)
    v2c = bitstream.pack_chunked(*map(np.asarray, ch), chunk_size=chunk,
                                 n_symbols=t, checksums=True)
    v2n = bitstream.pack_chunked(*map(np.asarray, ch), chunk_size=chunk,
                                 n_symbols=t, checksums=False)
    return {"blobs": {"v1": v1, "v2_crc": v2c, "v2_nocrc": v2n},
            "tbl": tbl, "syms": syms, "t": t, "chunk": chunk}


@pytest.fixture(scope="module")
def corpus():
    return _make_corpus()


@pytest.fixture(scope="module")
def blobs(corpus):
    return corpus["blobs"]


def _reader(name):
    return bitstream.unpack if name == "v1" else bitstream.unpack_chunked


def _must_only_value_error(read, blob):
    """The no-crash contract: success or ValueError, nothing else."""
    try:
        read(blob)
        return None
    except ValueError as e:
        return e
    # any other exception type propagates and fails the test


# ---------------------------------------------------------------------------
# truncations: every prefix must raise a named error, never crash
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["v1", "v2_crc", "v2_nocrc"])
def test_truncation_fuzz(blobs, name):
    blob, read = blobs[name], _reader(name)
    cuts = {0, 1, 3, 4, 7, len(blob) - 1}
    for rng in sweep(91, 40):
        cuts.add(int(ints(rng, 0, len(blob) - 1)))
    for cut in sorted(cuts):
        with pytest.raises(ValueError,
                           match="truncated|not a RAS|unsupported"):
            read(blob[:cut])


def test_truncation_errors_name_the_region(blobs):
    blob = blobs["v2_crc"]
    with pytest.raises(ValueError, match="header"):
        bitstream.unpack_chunked(blob[:10])
    with pytest.raises(ValueError, match="chunk index"):
        bitstream.unpack_chunked(blob[:bitstream._HEADER_V2.size + 5])
    with pytest.raises(ValueError, match=r"chunk \d+, lane \d+"):
        bitstream.unpack_chunked(blob[:len(blob) - 3])
    v1 = blobs["v1"]
    with pytest.raises(ValueError, match="lane"):
        bitstream.unpack(v1[:len(v1) - 3])


# ---------------------------------------------------------------------------
# byte flips: no-crash everywhere; CRC'd payloads must be caught by cell
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["v1", "v2_crc", "v2_nocrc"])
def test_header_and_body_flip_fuzz(blobs, name):
    """Flip one byte anywhere (header, index/length table, payload): the
    reader either parses or raises ValueError — no other exception type."""
    blob, read = blobs[name], _reader(name)
    for rng in sweep(92, 120):
        pos = int(ints(rng, 0, len(blob) - 1))
        bit = int(ints(rng, 0, 7))
        mut = bytearray(blob)
        mut[pos] ^= 1 << bit
        _must_only_value_error(read, bytes(mut))


def test_payload_flip_always_caught_with_checksums(blobs):
    """FLAG_CHUNK_CRC32: every payload byte is inside some cell's CRC, so
    every payload flip raises AND names the damaged (chunk, lane)."""
    blob = blobs["v2_crc"]
    _, _, meta = bitstream.unpack_chunked(blob)
    cells = meta.n_chunks * meta.lanes
    base = (bitstream._HEADER_V2.size
            + cells * bitstream._INDEX_V2C_DT.itemsize)
    positions = {base, len(blob) - 1}
    for rng in sweep(93, 60):
        positions.add(int(ints(rng, base, len(blob) - 1)))
    for pos in sorted(positions):
        mut = bytearray(blob)
        mut[pos] ^= 1 << int(pos % 8)
        with pytest.raises(ValueError, match=r"chunk \d+, lane \d+"):
            bitstream.unpack_chunked(bytes(mut))


def test_wrong_crc_cell_is_named(blobs):
    """Overwrite stored CRC cells with wrong values: the reader names the
    exact (chunk, lane) of every tampered cell."""
    blob = blobs["v2_crc"]
    _, _, meta = bitstream.unpack_chunked(blob)
    rec = bitstream._INDEX_V2C_DT.itemsize
    for rng in sweep(94, 12):
        cell = int(ints(rng, 0, meta.n_chunks * meta.lanes - 1))
        c, lane = divmod(cell, meta.lanes)
        off = bitstream._HEADER_V2.size + cell * rec + 12  # crc field
        mut = bytearray(blob)
        mut[off:off + 4] = bytes(x ^ 0x5A for x in mut[off:off + 4])
        with pytest.raises(ValueError,
                           match=rf"chunk {c}, lane {lane}"):
            bitstream.unpack_chunked(bytes(mut))


def test_uncorrupted_blobs_still_unpack(blobs):
    """Sanity: the fuzz fixtures themselves are healthy."""
    buf, start, meta = bitstream.unpack(blobs["v1"])
    assert meta.lanes == buf.shape[0]
    for name in ("v2_crc", "v2_nocrc"):
        buf, start, meta = bitstream.unpack_chunked(blobs[name])
        assert buf.shape[:2] == (meta.n_chunks, meta.lanes)


def test_index_offset_wrap_is_named(blobs):
    """Flip the HIGH byte of an index cell's u64 offset: the value must be
    rejected as an unsigned out-of-bounds offset, not cast to int64 (where
    it wraps negative, slips past a signed span check, and either crashes
    the payload gather with a raw IndexError or silently reads the wrong
    bytes)."""
    blob = blobs["v2_nocrc"]
    rec = bitstream._INDEX_V2_DT.itemsize
    for cell in (0, 2):
        off = bitstream._HEADER_V2.size + cell * rec + 7  # offset MSB
        for bit in (0, 7):
            mut = bytearray(blob)
            mut[off] ^= 1 << bit
            with pytest.raises(ValueError, match=r"chunk \d+, lane \d+"):
                bitstream.unpack_chunked(bytes(mut))


def test_overlapping_spans_refuse_giant_allocation(blobs):
    """A crafted index whose cells all alias the full payload (individually
    in bounds, collectively absurd) must be refused before the dense
    (n_chunks, lanes, cap) buffer is allocated."""
    blob = blobs["v2_nocrc"]
    _, _, meta = bitstream.unpack_chunked(blob)
    rec = bitstream._INDEX_V2_DT.itemsize
    cells = meta.n_chunks * meta.lanes
    base = bitstream._HEADER_V2.size + cells * rec
    payload_len = len(blob) - base
    mut = bytearray(blob)
    for cell in range(cells):   # every cell: offset 0, length = payload
        off = bitstream._HEADER_V2.size + cell * rec
        mut[off:off + 8] = (0).to_bytes(8, "little")
        mut[off + 8:off + 12] = payload_len.to_bytes(4, "little")
    with pytest.raises(ValueError, match="overlapping|inflated"):
        bitstream.unpack_chunked(bytes(mut))


def test_index_length_inflation_is_bounded(blobs):
    """Inflate one index length field: the reader must refuse with a named
    span error before trusting it (no giant allocation, no wrap)."""
    blob = blobs["v2_nocrc"]
    rec = bitstream._INDEX_V2_DT.itemsize
    for cell in (0, 3):
        off = bitstream._HEADER_V2.size + cell * rec + 8  # length field
        mut = bytearray(blob)
        mut[off:off + 4] = (0xFFFFFFF0).to_bytes(4, "little")
        with pytest.raises(ValueError, match=r"chunk \d+, lane \d+"):
            bitstream.unpack_chunked(bytes(mut))


# ---------------------------------------------------------------------------
# kernel-backend column: the zero-copy decode front door
# (``parse_chunked`` -> ``from_container``) under the same corruptions —
# mutated blobs surface the SAME named ValueErrors as the host reader, and
# a hostile index can never make the kernel read out of the slab
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("name", ["v1", "v2_crc", "v2_nocrc"])
def test_kernel_front_door_truncation_fuzz(blobs, name):
    """Every truncation the host reader rejects, ``parse_chunked`` rejects
    with the identical named error (shared validation, one source)."""
    blob, read = blobs[name], _reader(name)
    cuts = {0, 1, 3, 4, 7, len(blob) - 1}
    for rng in sweep(95, 25):
        cuts.add(int(ints(rng, 0, len(blob) - 1)))
    for cut in sorted(cuts):
        host = _must_only_value_error(read, blob[:cut])
        kern = _must_only_value_error(bitstream.parse_chunked, blob[:cut])
        assert host is not None and kern is not None, cut
        assert str(host) == str(kern), cut


@pytest.mark.parametrize("name", ["v1", "v2_crc", "v2_nocrc"])
def test_kernel_front_door_flip_fuzz(corpus, name):
    """One-byte flips: ``parse_chunked`` accepts/rejects exactly when the
    host reader does, raising the identical named ValueError on reject; on
    every accepted mutant the kernel decode from the packed slab returns
    the same symbols as the coder decode of the host-unpacked dense stream
    — garbage in equals garbage out, NEVER an out-of-bounds read."""
    from repro.kernels import ops
    blob, read = corpus["blobs"][name], _reader(name)
    tbl, t, chunk = corpus["tbl"], corpus["t"], corpus["chunk"]
    checked = 0
    for rng in sweep(96, 80):
        pos = int(ints(rng, 0, len(blob) - 1))
        bit = int(ints(rng, 0, 7))
        mut = bytearray(blob)
        mut[pos] ^= 1 << bit
        mut = bytes(mut)
        host = _must_only_value_error(read, mut)
        kern = _must_only_value_error(bitstream.parse_chunked, mut)
        assert (host is None) == (kern is None), (pos, bit)
        if host is not None:
            assert str(host) == str(kern), (pos, bit)
        elif checked < 4 and name != "v1":
            cs = bitstream.parse_chunked(mut)
            dense = bitstream.slab_to_chunked(cs)
            csym, _ = coder.decode_chunked(dense, t, tbl, chunk)
            ksym, _ = ops.rans_decode_chunked(
                n_symbols=t, tbl=tbl, chunk_size=chunk, from_container=cs)
            assert np.array_equal(np.asarray(csym), np.asarray(ksym)), (
                pos, bit)
            checked += 1
    if name != "v1":
        assert checked > 0, "sweep produced no accepted mutants to decode"


def test_kernel_span_clamp_never_reads_out_of_slab(corpus):
    """Defense in depth behind ``parse_chunked``: a ContainerSlab whose
    index was poisoned AFTER validation (offsets past the payload end,
    lengths past the window) must still decode without an OOB access
    (which interpret mode would raise on) — the host-side base clip plus
    the in-kernel span clamp turn every hostile (offset, length) into
    in-bounds reads of zero-padded windows.  Since the over-read bugfix
    the hostile windows are also *detectable*: the zero-injected refills
    raise the per-lane underflow counters, surfaced via
    ``exhausted_flags=True`` (the host entry would raise the named
    ``StreamExhaustedError`` instead of returning garbage)."""
    from repro.core.coder import StreamExhaustedError
    from repro.kernels import ops
    cs = bitstream.parse_chunked(corpus["blobs"]["v2_nocrc"])
    tbl, t, chunk = corpus["tbl"], corpus["t"], corpus["chunk"]
    s = cs.slab.shape[0]
    poisons = {
        "offset_past_end": cs._replace(
            offset=np.full_like(cs.offset, s + 1000)),
        "length_past_window": cs._replace(
            length=np.full_like(cs.length, cs.cap + 7)),
        "both_hostile": cs._replace(
            offset=np.full_like(cs.offset, s - 1),
            length=np.full_like(cs.length, cs.cap + 3)),
    }
    for name, bad in poisons.items():
        sym, _, under = ops.rans_decode_chunked(
            n_symbols=t, tbl=tbl, chunk_size=chunk, from_container=bad,
            exhausted_flags=True)
        assert np.asarray(sym).shape == corpus["syms"].shape, name
    # the fully-hostile offsets are not just clamped but FLAGGED — and the
    # raising host entry turns them into the named error
    with pytest.raises(StreamExhaustedError):
        ops.rans_decode_chunked(n_symbols=t, tbl=tbl, chunk_size=chunk,
                                from_container=poisons["offset_past_end"])
