"""Blockstore: roundtrip, on-demand ranges, read amplification (Fig. 20).

Property-based variants require ``hypothesis`` and are skipped when it is
absent; deterministic example-based equivalents always run.
"""
import math
import os
import random

import numpy as np
import pytest

from repro.core import BlockReader, read_manifest, write_blockstore
from repro.core.blockstore import ReadStats, default_codec, have_zstd

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - exercised on bare interpreters
    HAVE_HYPOTHESIS = False


def test_roundtrip(tmp_path):
    payload = os.urandom(1_000_000)
    path = str(tmp_path / "p.blocks")
    m = write_blockstore(payload, path, block_size=64 * 1024)
    assert m.raw_size == len(payload)
    assert m.n_blocks == -(-len(payload) // (64 * 1024))
    r = BlockReader(path)
    assert r.read_all() == payload


def test_manifest_reload(tmp_path):
    payload = b"hello" * 10_000
    path = str(tmp_path / "p.blocks")
    m = write_blockstore(payload, path, block_size=8192)
    m2 = read_manifest(path)
    assert m2 == m
    assert m2.codec == default_codec()


def test_zlib_codec_roundtrip(tmp_path):
    """The stdlib fallback codec must roundtrip regardless of zstd presence."""
    payload = os.urandom(300_000)
    path = str(tmp_path / "p.blocks")
    m = write_blockstore(payload, path, block_size=32 * 1024, codec="zlib")
    assert m.codec == "zlib"
    assert read_manifest(path).codec == "zlib"
    assert BlockReader(path).read_all() == payload


@pytest.mark.skipif(not have_zstd(), reason="zstandard not installed")
def test_zstd_codec_roundtrip(tmp_path):
    payload = os.urandom(300_000)
    path = str(tmp_path / "p.blocks")
    m = write_blockstore(payload, path, block_size=32 * 1024, codec="zstd")
    assert m.codec == "zstd"
    assert BlockReader(path).read_all() == payload


def test_unknown_codec_raises(tmp_path):
    with pytest.raises(ValueError):
        write_blockstore(b"x", str(tmp_path / "p.blocks"), codec="lz77")


def test_range_read_exact(tmp_path):
    payload = bytes(range(256)) * 4096  # 1 MiB deterministic
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=32 * 1024)
    r = BlockReader(path)
    assert r.read_range(100_000, 50_000) == payload[100_000:150_000]
    assert r.read_range(0, 1) == payload[:1]
    assert r.read_range(len(payload) - 7, 7) == payload[-7:]


def test_out_of_range_raises(tmp_path):
    path = str(tmp_path / "p.blocks")
    write_blockstore(b"x" * 100, path, block_size=64)
    r = BlockReader(path)
    with pytest.raises(ValueError):
        r.read_range(90, 20)


def test_on_demand_fetches_only_covering_blocks(tmp_path):
    payload = os.urandom(1 << 20)
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=64 * 1024)  # 16 blocks
    r = BlockReader(path)
    r.read_range(0, 1000)  # one block
    assert r.stats.blocks_fetched == 1
    r.read_range(60_000, 10_000)  # spans blocks 0-1; block 0 cached
    assert r.stats.blocks_fetched == 2


def test_read_amplification_grows_with_block_size(tmp_path):
    """Paper Fig. 20: bigger blocks => more useless bytes at range edges."""
    payload = os.urandom(16 << 20)
    amps = []
    for bs in (64 * 1024, 512 * 1024, 2 << 20):
        path = str(tmp_path / f"p{bs}.blocks")
        write_blockstore(payload, path, block_size=bs)
        r = BlockReader(path)
        # stride > largest block so no read hits a cached block
        for off in range(0, len(payload) - 1000, 3_000_000):
            r.read_range(off, 1000)
        amps.append(r.stats.amplification())
    assert amps[0] < amps[1] < amps[2]


def test_block_cache_counts_network_bytes_once(tmp_path):
    payload = os.urandom(256 * 1024)
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=64 * 1024)
    r = BlockReader(path)
    r.read_range(0, 1000)
    first = r.stats.fetched_compressed
    r.read_range(500, 1000)  # same block, cached
    assert r.stats.fetched_compressed == first


# ----------------------------------------------------------------------
# Deterministic example-based variants of the property tests: always run,
# even without hypothesis (seeded random, fixed corner cases).
# ----------------------------------------------------------------------
def test_roundtrip_examples(tmp_path):
    rng = random.Random(42)
    cases = [
        (b"\x00", 1024),
        (b"a" * 1023, 1024),
        (b"b" * 1024, 1024),
        (b"c" * 1025, 1024),
        (rng.randbytes(199_999), 4096),
        (rng.randbytes(65_536), 65536),
        (bytes(range(256)) * 300, 1024),
    ]
    for i, (data, block_size) in enumerate(cases):
        path = str(tmp_path / f"p{i}.blocks")
        write_blockstore(data, path, block_size=block_size)
        assert BlockReader(path).read_all() == data, (i, len(data), block_size)


def test_arbitrary_range_examples(tmp_path):
    rng = random.Random(7)
    payload = rng.randbytes(100_000)
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=4096)
    r = BlockReader(path)
    ranges = [(0, 0), (0, 1), (0, len(payload)), (len(payload) - 1, 1), (4095, 2)]
    ranges += [
        (rng.randrange(len(payload)), 0) for _ in range(5)
    ]
    for _ in range(40):
        off = rng.randrange(len(payload))
        ranges.append((off, rng.randrange(len(payload) - off + 1)))
    for off, ln in ranges:
        assert r.read_range(off, ln) == payload[off : off + ln], (off, ln)


# ----------------------------------------------------------------------
# hypothesis property tests (skipped without the package)
# ----------------------------------------------------------------------
if HAVE_HYPOTHESIS:

    @settings(max_examples=25, deadline=None)
    @given(
        data=st.binary(min_size=1, max_size=200_000),
        block_size=st.sampled_from([1024, 4096, 65536]),
    )
    def test_roundtrip_property(tmp_path_factory, data, block_size):
        path = str(tmp_path_factory.mktemp("bs") / "p.blocks")
        write_blockstore(data, path, block_size=block_size)
        assert BlockReader(path).read_all() == data

    @settings(max_examples=25, deadline=None)
    @given(st.data())
    def test_arbitrary_range_property(tmp_path_factory, data):
        payload = data.draw(st.binary(min_size=10, max_size=100_000))
        path = str(tmp_path_factory.mktemp("bs") / "p.blocks")
        write_blockstore(payload, path, block_size=4096)
        r = BlockReader(path)
        off = data.draw(st.integers(0, len(payload) - 1))
        ln = data.draw(st.integers(0, len(payload) - off))
        assert r.read_range(off, ln) == payload[off : off + ln]


# ----------------------------------------------------------------------
# PR 2: persistent handle + coalesced reads
# ----------------------------------------------------------------------
def test_cold_sequential_range_coalesces_to_one_read(tmp_path):
    payload = os.urandom(400_000)
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=32 * 1024)
    r = BlockReader(path)
    got = r.read_range(0, 300_000)  # covers 10 uncached blocks
    assert got == payload[:300_000]
    assert r.stats.blocks_fetched == 10
    assert r.file_reads == 1  # one seek+read for the whole contiguous run


def test_coalescing_splits_around_cached_blocks(tmp_path):
    payload = os.urandom(10 * 8192)
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=8192)
    r = BlockReader(path)
    r.get_block(4)  # warm the middle block
    assert r.file_reads == 1
    out = r.read_range(0, len(payload))
    assert out == payload
    # blocks 0-3 and 5-9 are two contiguous uncached runs
    assert r.file_reads == 3
    assert r.stats.blocks_fetched == 10  # accounting identical to per-block path


def test_coalesced_stats_match_per_block_path(tmp_path):
    payload = os.urandom(123_456)
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=4096)
    a, b = BlockReader(path), BlockReader(path)
    a.read_range(1000, 100_000)  # coalesced
    first, last = b.manifest.block_range_for(1000, 100_000)
    b.stats.useful_bytes += 100_000
    for i in range(first, last + 1):  # the old per-block fetch order
        b.get_block(i)
    assert (a.stats.useful_bytes, a.stats.fetched_compressed,
            a.stats.fetched_raw, a.stats.blocks_fetched) == (
        b.stats.useful_bytes, b.stats.fetched_compressed,
        b.stats.fetched_raw, b.stats.blocks_fetched)
    assert a.stats.amplification() == b.stats.amplification()


# ----------------------------------------------------------------------
# PR 8: guard + codec-default bugfixes, edge-case coverage
# ----------------------------------------------------------------------
def test_negative_length_rejected(tmp_path):
    """Regression: read_range(5, -3) used to pass the guard and *decrement*
    useful_bytes, corrupting amplification()."""
    payload = os.urandom(10_000)
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=1024)
    r = BlockReader(path)
    r.read_range(0, 1000)
    before = r.stats.useful_bytes
    with pytest.raises(ValueError):
        r.read_range(5, -3)
    assert r.stats.useful_bytes == before  # stats untouched by the rejection
    assert r.read_range(5, 0) == b""  # zero length stays a valid no-op


def test_default_level_is_per_codec(tmp_path, monkeypatch):
    """Regression: level defaulted to zstd's 3 and was forced onto the zlib
    fallback, under-compressing vs _ZlibCodec's documented default 6."""
    from repro.core import blockstore as bs

    payload = (bytes(range(256)) * 2000) + os.urandom(100_000)
    default = str(tmp_path / "default.blocks")
    pinned6 = str(tmp_path / "pinned6.blocks")
    m_default = write_blockstore(payload, default, block_size=64 * 1024, codec="zlib")
    m_pinned = write_blockstore(
        payload, pinned6, block_size=64 * 1024, codec="zlib", level=6
    )
    # zlib default is 6: an unpinned write must match an explicit level-6 one
    # (the old code silently wrote level 3 here).
    assert m_default.offsets == m_pinned.offsets
    import zlib

    blob = payload[: 64 * 1024]
    assert m_default.block_compressed_size(0) == len(zlib.compress(blob, 6))
    if have_zstd():
        m_zstd = write_blockstore(payload, str(tmp_path / "z.blocks"), block_size=64 * 1024, codec="zstd")
        m_zstd3 = write_blockstore(
            payload, str(tmp_path / "z3.blocks"), block_size=64 * 1024, codec="zstd", level=3
        )
        assert m_zstd.offsets == m_zstd3.offsets  # zstd default is 3


def test_empty_payload_roundtrip(tmp_path):
    path = str(tmp_path / "empty.blocks")
    m = write_blockstore(b"", path, block_size=1024)
    assert m.raw_size == 0
    assert m.n_blocks == 1  # format always carries >= 1 block
    r = BlockReader(path)
    assert r.read_all() == b""
    assert r.read_range(0, 0) == b""


def test_read_range_at_exact_block_boundaries(tmp_path):
    payload = bytes(range(256)) * 64  # 16 KiB
    bs = 4096
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=bs)
    r = BlockReader(path)
    # exactly one block, starting on a boundary
    assert r.read_range(bs, bs) == payload[bs : 2 * bs]
    assert r.stats.blocks_fetched == 1
    # range ending exactly on a boundary must not touch the next block
    r2 = BlockReader(path)
    assert r2.read_range(0, bs) == payload[:bs]
    assert r2.stats.blocks_fetched == 1
    # one byte past the boundary pulls exactly one extra block
    r3 = BlockReader(path)
    assert r3.read_range(0, bs + 1) == payload[: bs + 1]
    assert r3.stats.blocks_fetched == 2


def test_closed_reader_read_range_raises(tmp_path):
    payload = os.urandom(10_000)
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=1024)
    r = BlockReader(path)
    r.close()
    with pytest.raises(ValueError):
        r.read_range(0, 100)


def test_fetch_run_splits_on_cached_hole(tmp_path):
    """_fetch_run over [0..9] with block 5 cached must issue two coalesced
    file reads (0-4 and 6-9), not ten."""
    payload = os.urandom(10 * 4096)
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=4096)
    r = BlockReader(path)
    r.get_block(5)
    reads_before = r.file_reads
    r._fetch_run(0, 9)
    assert r.file_reads - reads_before == 2
    assert r.stats.blocks_fetched == 10
    assert r.read_range(0, len(payload)) == payload  # all cached now
    assert r.stats.blocks_fetched == 10  # and no refetches


def test_reader_close_and_context_manager(tmp_path):
    payload = os.urandom(50_000)
    path = str(tmp_path / "p.blocks")
    write_blockstore(payload, path, block_size=8192)
    with BlockReader(path) as r:
        assert r.read_range(0, 1000) == payload[:1000]
    with pytest.raises(ValueError):
        r.fetch_block_compressed(0)  # closed handle refuses cleanly
    r.close()  # idempotent


# ----------------------------------------------------------------------
# In-place assembly: read_range_into
# ----------------------------------------------------------------------
BS = 4096
INTO_PAYLOAD = random.Random(11).randbytes(10 * BS + 123)
INTO_RANGES = {
    "inside_one_block": (BS + 100, 500),
    "across_block_edges": (BS - 7, 2 * BS + 14),
    "on_block_boundaries": (2 * BS, 3 * BS),
    "zero_length": (3 * BS + 5, 0),
    "whole_payload": (0, len(INTO_PAYLOAD)),
}


@pytest.fixture
def into_path(tmp_path):
    path = str(tmp_path / "p.blocks")
    write_blockstore(INTO_PAYLOAD, path, block_size=BS)
    return path


@pytest.mark.parametrize("cached", [False, True], ids=["cold", "cached"])
@pytest.mark.parametrize("case", list(INTO_RANGES))
def test_read_range_into_matches_read_range(into_path, case, cached):
    off, ln = INTO_RANGES[case]
    r = BlockReader(into_path)
    if cached:
        r.read_all()
    out = np.empty(ln, np.uint8)
    r.read_range_into(off, ln, out)
    assert out.tobytes() == INTO_PAYLOAD[off : off + ln]
    assert r.read_range(off, ln) == INTO_PAYLOAD[off : off + ln]


def test_read_range_into_fills_a_leaf_array(into_path):
    r = BlockReader(into_path)
    leaf = np.empty((3, 5), np.float32)  # 60 bytes across the first block edge
    r.read_range_into(BS - 30, leaf.nbytes, leaf)
    assert leaf.tobytes() == INTO_PAYLOAD[BS - 30 : BS + 30]


def test_read_range_into_accounts_as_read_range(into_path):
    a, b = BlockReader(into_path), BlockReader(into_path)
    for r in (a, b):
        r.get_block(5)  # a cached hole splits the coalesced reads
    for off, ln in [*INTO_RANGES.values(), (BS + 1, 6 * BS)]:
        a.read_range(off, ln)
        b.read_range_into(off, ln, bytearray(ln))
        assert b.stats == a.stats and b.file_reads == a.file_reads, (off, ln)


def test_read_range_into_rejects_a_wrong_buffer(into_path):
    r = BlockReader(into_path)
    for out in (bytearray(99), bytearray(101), np.empty(26, np.float32), bytes(100)):
        with pytest.raises(ValueError):
            r.read_range_into(0, 100, out)
    assert r.stats == ReadStats() and r.file_reads == 0  # nothing fetched


def test_read_range_into_places_whole_blocks_and_keeps_the_edges(into_path):
    """Blocks the range covers whole go straight into the buffer and are not
    kept; the two it covers in part stay cached for the neighbouring range.
    Reading the range again counts no block twice."""
    r = BlockReader(into_path)
    off, ln = INTO_RANGES["across_block_edges"]  # blocks 0 and 3 in part, 1-2 whole
    out = bytearray(ln)
    r.read_range_into(off, ln, out)
    assert bytes(out) == INTO_PAYLOAD[off : off + ln]
    assert sorted(r._cache) == [0, 3]
    stats = (r.stats.blocks_fetched, r.stats.fetched_compressed, r.stats.fetched_raw)
    assert stats[0] == 4
    assert r.read_range(off, ln) == INTO_PAYLOAD[off : off + ln]
    assert (r.stats.blocks_fetched, r.stats.fetched_compressed, r.stats.fetched_raw) == stats


def test_coalesced_reads_stop_at_max_run_bytes(into_path, monkeypatch):
    from repro.core import blockstore as bs

    r = BlockReader(into_path)
    m = r.manifest
    monkeypatch.setattr(bs, "MAX_RUN_BYTES", max(
        m.block_compressed_size(i) for i in range(m.n_blocks)))
    assert r.read_range(0, m.raw_size) == INTO_PAYLOAD
    assert r.file_reads == m.n_blocks  # a cap of one block reads block by block
    assert r.stats.blocks_fetched == m.n_blocks


def test_read_range_into_with_the_zlib_codec(tmp_path):
    path = str(tmp_path / "z.blocks")
    write_blockstore(INTO_PAYLOAD, path, block_size=BS, codec="zlib")
    r = BlockReader(path)
    for off, ln in INTO_RANGES.values():
        out = np.empty(ln, np.uint8)
        r.read_range_into(off, ln, out)
        assert out.tobytes() == INTO_PAYLOAD[off : off + ln], (off, ln)


# ----------------------------------------------------------------------
# A run's whole blocks decompressed on a pool of threads
# ----------------------------------------------------------------------
from repro import tracing  # noqa: E402
from repro.core import blockstore as bs  # noqa: E402

POOL_PAYLOAD = random.Random(17).randbytes(40 * BS + 321)  # 41 blocks
POOL_RANGES = {
    "whole_payload": (0, len(POOL_PAYLOAD)),
    "inside_blocks_at_both_ends": (BS + 99, 30 * BS + 7),
    "on_block_boundaries": (3 * BS, 20 * BS),
}
CODECS = ["zlib", pytest.param("zstd", marks=pytest.mark.skipif(
    not have_zstd(), reason="zstandard not installed"))]


@pytest.fixture(scope="module")
def pools():
    """The serial path (the calling thread alone) and a pool of four."""
    return bs._Pool(1), bs._Pool(4)


def pool_path(tmp_path, codec):
    path = str(tmp_path / f"{codec}.blocks")
    write_blockstore(POOL_PAYLOAD, path, block_size=BS, codec=codec)
    return path


def read_on(pool, monkeypatch, path, off, ln, hole=None):
    """``read_range_into`` on ``pool``: the reader, the bytes, the spans' totals."""
    monkeypatch.setattr(bs, "_shared_pool", lambda: pool)
    r = BlockReader(path)
    if hole is not None:
        r.get_block(hole)
    out = np.empty(ln, np.uint8)
    with tracing.span("test") as root:
        r.read_range_into(off, ln, out)
    return r, out.tobytes(), root.totals


@pytest.mark.parametrize("runs", ["one_read", "short_runs"])
@pytest.mark.parametrize("hole", [None, 12], ids=["no_hole", "cached_hole"])
@pytest.mark.parametrize("case", list(POOL_RANGES))
@pytest.mark.parametrize("codec", CODECS)
def test_pool_gives_the_serial_bytes_and_counts(tmp_path, monkeypatch, pools,
                                                codec, case, hole, runs):
    if runs == "short_runs":  # several runs, so the next is read ahead
        monkeypatch.setattr(bs, "MAX_RUN_BYTES", 5 * BS)
    path = pool_path(tmp_path, codec)
    off, ln = POOL_RANGES[case]
    serial, s_bytes, s_tot = read_on(pools[0], monkeypatch, path, off, ln, hole)
    pooled, p_bytes, p_tot = read_on(pools[1], monkeypatch, path, off, ln, hole)
    assert s_bytes == p_bytes == POOL_PAYLOAD[off : off + ln]
    assert pooled.stats == serial.stats
    assert pooled.file_reads == serial.file_reads
    assert sorted(pooled._cache) == sorted(serial._cache)
    for key in [("blockstore.read_range", "blocks_cached"),
                ("blockstore.decompress", "blocks"),
                ("blockstore.decompress", "raw_bytes"),
                ("blockstore.read", "bytes")]:
        assert p_tot[key] == s_tot[key], key
    assert s_tot["blockstore.decompress", "pool_blocks"] == 0
    assert p_tot["blockstore.decompress", "pool_blocks"] > 0


@pytest.mark.parametrize("codec", CODECS)
def test_the_next_run_is_read_before_the_pool_is_waited_for(
        tmp_path, monkeypatch, pools, codec):
    monkeypatch.setattr(bs, "MAX_RUN_BYTES", 5 * BS)
    monkeypatch.setattr(bs, "_shared_pool", lambda: pools[1])
    names = []

    def recording(name, **args):
        names.append(name)
        return tracing.span(name, **args)

    monkeypatch.setattr(bs, "span", recording)
    r = BlockReader(pool_path(tmp_path, codec))
    out = np.empty(len(POOL_PAYLOAD), np.uint8)
    r.read_range_into(0, len(POOL_PAYLOAD), out)
    assert out.tobytes() == POOL_PAYLOAD
    steps = [n.split(".")[1][0] for n in names[1:]]  # after read_range
    runs = r.file_reads
    assert runs > 2
    assert steps == ["r"] + ["r", "d"] * (runs - 1) + ["d"]


@pytest.mark.parametrize("where", ["worker", "caller"])
@pytest.mark.parametrize("codec", CODECS)
def test_a_corrupt_block_raises_value_error(tmp_path, monkeypatch, pools, codec, where):
    """41 whole blocks over four threads: the workers take blocks 0-29,
    the calling thread 30-40. One byte of the block's frame is flipped."""
    path = pool_path(tmp_path, codec)
    m = read_manifest(path)
    k = 15 if where == "worker" else 35
    at = bs._HEADER.size + 8 * (m.n_blocks + 1) + m.offsets[k]
    with open(path, "r+b") as f:
        f.seek(at)
        byte = f.read(1)[0]
        f.seek(at)
        f.write(bytes([byte ^ 0xFF]))
    monkeypatch.setattr(bs, "_shared_pool", lambda: pools[1])
    with pytest.raises(ValueError, match=f"block {k}"):
        BlockReader(path).read_range_into(0, m.raw_size, bytearray(m.raw_size))


@pytest.mark.parametrize("case, on_pool", [
    ("many_whole_blocks", True), ("one_block", False), ("two_part_blocks", False)])
def test_pool_blocks_counts_the_blocks_off_the_calling_thread(
        tmp_path, monkeypatch, pools, case, on_pool):
    off, ln = {"many_whole_blocks": (0, 20 * BS), "one_block": (2 * BS, BS),
               "two_part_blocks": (BS + 5, BS)}[case]
    _, got, totals = read_on(pools[1], monkeypatch, pool_path(tmp_path, "zlib"), off, ln)
    assert got == POOL_PAYLOAD[off : off + ln]
    assert (totals["blockstore.decompress", "pool_blocks"] > 0) == on_pool


@pytest.mark.parametrize("quota, width", [(math.inf, None), (2.5, 2), (0.5, 1)])
def test_pool_width_follows_affinity_and_quota(monkeypatch, quota, width):
    monkeypatch.setattr(bs, "_cgroup_cpus", lambda: quota)
    cpus = len(os.sched_getaffinity(0))
    want = min(bs._POOL_MAX, cpus) if width is None else min(width, cpus)
    assert bs._pool_width() == want


def test_concurrent_readers_share_one_pool(tmp_path, monkeypatch, pools):
    """More calling threads than cores, each reading its own range through
    one pool of four, with the interpreter switching threads often."""
    import sys
    import threading

    path = pool_path(tmp_path, "zlib")
    monkeypatch.setattr(bs, "_shared_pool", lambda: pools[1])
    ranges = [(k * 97, len(POOL_PAYLOAD) - k * 1000) for k in range(2 * os.cpu_count() + 2)]
    got = {}

    def read(off, ln):
        out = np.empty(ln, np.uint8)
        BlockReader(path).read_range_into(off, ln, out)
        got[off, ln] = out.tobytes()

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=read, args=r) for r in ranges]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not any(t.is_alive() for t in threads)
    for off, ln in ranges:
        assert got[off, ln] == POOL_PAYLOAD[off : off + ln], (off, ln)


@pytest.mark.parametrize("files, cpus", [
    ({}, math.inf),
    ({"/sys/fs/cgroup/cpu.max": "max 100000"}, math.inf),
    ({"/sys/fs/cgroup/fn/cpu.max": "250000 100000"}, 2.5),
    ({"/sys/fs/cgroup/cpu/fn/cpu.cfs_quota_us": "150000",
      "/sys/fs/cgroup/cpu/fn/cpu.cfs_period_us": "100000"}, 1.5),
    ({"/sys/fs/cgroup/cpu/cpu.cfs_quota_us": "-1",
      "/sys/fs/cgroup/cpu/cpu.cfs_period_us": "100000"}, math.inf),
], ids=["none", "v2_max", "v2_quota", "v1_quota", "v1_none"])
def test_cgroup_quota_is_read_in_cpus(monkeypatch, files, cpus):
    files = {"/proc/self/cgroup": "0::/fn\n4:cpu,cpuacct:/fn\n", **files}
    monkeypatch.setattr(bs, "_words", lambda p: files[p].split() if p in files else [])
    assert bs._cgroup_cpus() == cpus


def test_a_closed_readers_run_buffers_go_to_the_next_reader(into_path, monkeypatch):
    monkeypatch.setattr(bs, "_spare_runs", [])
    a = BlockReader(into_path)
    assert a.read_range(0, len(INTO_PAYLOAD)) == INTO_PAYLOAD
    bufs = a._run_bufs
    b = BlockReader(into_path)
    assert b._run_bufs is not bufs  # open readers share none
    a.close()
    b.close()  # one pair is kept: a's
    c = BlockReader(into_path)
    assert c._run_bufs is bufs
    assert c.read_range(0, len(INTO_PAYLOAD)) == INTO_PAYLOAD
