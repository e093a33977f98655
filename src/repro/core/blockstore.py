"""I/O-efficient block format with on-demand fetch (paper §3.5).

Original payload (a container image in the paper; a checkpoint shard / code
package here) is split into fixed-size blocks, each compressed *separately*,
and written back-to-back.  An offset table records where each compressed
block begins, so a reader can satisfy an arbitrary ``(offset, length)``
range request by touching only ``ceil`` of the covering blocks — the
on-demand I/O mechanism.  Reads must align to block boundaries, which
causes bounded *read amplification* at the two ends of the range (paper
§4.6); :meth:`BlockReader.read_range` reports both useful and fetched bytes
so benchmarks can reproduce Figure 20.

Layout of a blockstore file::

    [magic u32][version u32][block_size u64][n_blocks u64][raw_size u64]
    [offset table: (n_blocks + 1) * u64]          # offsets into data area
    [compressed block 0][compressed block 1]...

Compression codec: zstd when the ``zstandard`` package is available (the
paper's production choice), with a pure-stdlib ``zlib`` fallback so the
format — and everything layered on it — works on a bare interpreter.  The
codec is encoded in the header ``version`` field (1 = zstd, 2 = zlib), so
readers always know how a file was written; reading a zstd file without
``zstandard`` installed raises a clear error instead of corrupt output.

The format is used by three layers:
  * ``checkpoint/`` — every checkpoint shard is a blockstore file;
  * ``core/provisioning.py`` / ``sim/`` — the unit streamed down an FT edge
    is one (compressed) block;
  * code-package distribution (paper §4.5) — same format, same path.
"""
from __future__ import annotations

import math
import os
import queue
import struct
import threading
import zlib
from concurrent.futures import Future
from dataclasses import dataclass, field

from repro.tracing import span

try:  # optional: zstd is the production codec, zlib the stdlib fallback
    import zstandard as _zstd
except ImportError:  # pragma: no cover - exercised on bare interpreters
    _zstd = None

MAGIC = 0xFAA5_0001
# Header ``version`` doubles as the codec id so old files stay readable.
VERSION_ZSTD = 1
VERSION_ZLIB = 2
VERSION = VERSION_ZSTD  # kept for backwards compatibility of the constant
DEFAULT_BLOCK_SIZE = 512 * 1024  # paper's production setting (512 KB)
MAX_RUN_BYTES = 32 << 20  # compressed bytes a coalesced read takes at most

_CODEC_BY_VERSION = {VERSION_ZSTD: "zstd", VERSION_ZLIB: "zlib"}
_VERSION_BY_CODEC = {v: k for k, v in _CODEC_BY_VERSION.items()}

_HEADER = struct.Struct("<IIQQQ")
_POOL_MAX = 8  # a run's whole blocks go to at most this many threads


def have_zstd() -> bool:
    return _zstd is not None


def default_codec() -> str:
    return "zstd" if _zstd is not None else "zlib"


class _ZstdCodec:
    name = "zstd"

    def __init__(self, level: int = 3) -> None:
        if _zstd is None:
            raise RuntimeError(
                "file requires the 'zstandard' package (codec zstd), which is "
                "not installed; re-write the payload with codec='zlib'"
            )
        self._c = _zstd.ZstdCompressor(level=level)
        self._d = _zstd.ZstdDecompressor()

    def compress(self, data: bytes) -> bytes:
        return self._c.compress(data)

    def decompress(self, data: bytes, raw_size: int) -> bytes:
        return self._d.decompress(data, max_output_size=raw_size)

    def decompress_into(self, data, out: memoryview) -> None:
        """One frame, straight into ``out`` (exactly its raw size)."""
        reader = self._d.stream_reader(data)
        n = 0
        while n < len(out):
            got = reader.readinto(out[n:])
            if not got:
                break
            n += got
        if n != len(out) or reader.read(1):
            raise ValueError(f"block decompressed to other than {len(out)} bytes")


class _ZlibCodec:
    name = "zlib"

    def __init__(self, level: int = 6) -> None:
        self._level = min(max(level, 0), 9)

    def compress(self, data: bytes) -> bytes:
        return zlib.compress(data, self._level)

    def decompress(self, data: bytes, raw_size: int) -> bytes:
        out = zlib.decompress(data, bufsize=max(raw_size, 1))
        if len(out) > raw_size:
            raise ValueError(f"block decompressed to {len(out)} > {raw_size} bytes")
        return out

    def decompress_into(self, data, out: memoryview) -> None:
        raw = self.decompress(data, len(out))
        if len(raw) != len(out):
            raise ValueError(f"block decompressed to {len(raw)}, not {len(out)} bytes")
        out[:] = raw


def _make_codec(name: str, level: int | None = None):
    """Build a codec; ``level=None`` means the codec's own default (zstd 3 / zlib 6)."""
    if name == "zstd":
        return _ZstdCodec() if level is None else _ZstdCodec(level)
    if name == "zlib":
        return _ZlibCodec() if level is None else _ZlibCodec(level)
    raise ValueError(f"unknown blockstore codec {name!r}")


_CODEC_ERRORS = (zlib.error,) + ((_zstd.ZstdError,) if _zstd is not None else ())
_per_thread = threading.local()  # each thread's decompression contexts, by codec


def _thread_codec(name: str):
    """This thread's own codec ``name``: a ``ZstdDecompressor`` is not safe
    to share between threads."""
    codec = getattr(_per_thread, name, None)
    if codec is None:
        codec = _make_codec(name)
        setattr(_per_thread, name, codec)
    return codec


def _decompress_blocks(codec_name: str, path: str, items: list) -> None:
    """Each ``(block, compressed, out)`` of ``items`` into its ``out``; a
    corrupt block or one of another size raises ``ValueError``."""
    codec = _thread_codec(codec_name)
    for i, comp, out in items:
        try:
            codec.decompress_into(comp, out)
        except (ValueError, *_CODEC_ERRORS) as e:
            raise ValueError(f"{path}: block {i}: {e}") from e


def _words(path: str) -> list[str]:
    try:
        with open(path) as f:
            return f.read().split()
    except OSError:
        return []


def _cgroup_cpus() -> float:
    """CPUs the quota of the process's cgroup allows (v2 ``cpu.max``, v1
    ``cpu.cfs_quota_us`` over ``cpu.cfs_period_us``; at the cgroup's own
    path and at the mount's root), ``inf`` where none is set."""
    cpus = math.inf
    dirs = {""} | {e.split(":", 2)[2].rstrip("/") for e in _words("/proc/self/cgroup")}
    for d in dirs:
        v1 = f"/sys/fs/cgroup/cpu{d}/cpu.cfs_"
        for quota in (_words(f"/sys/fs/cgroup{d}/cpu.max"),
                      _words(v1 + "quota_us") + _words(v1 + "period_us")):
            if len(quota) == 2 and quota[0] not in ("max", "-1"):
                cpus = min(cpus, int(quota[0]) / int(quota[1]))
    return cpus


def _pool_width() -> int:
    """Threads a run's whole blocks are split over, the calling thread's
    included: the CPUs this process may run on, by affinity and by a
    cgroup quota where that is lower, at most ``_POOL_MAX``."""
    cpus = len(os.sched_getaffinity(0)) if hasattr(os, "sched_getaffinity") else os.cpu_count()
    return max(1, int(min(_POOL_MAX, cpus or 1, _cgroup_cpus())))


class _Pool:
    """``width - 1`` daemon threads that run tasks beside the calling thread."""

    def __init__(self, width: int) -> None:
        self.width = width
        self._tasks: queue.SimpleQueue = queue.SimpleQueue()
        for n in range(width - 1):
            threading.Thread(target=self._serve, name=f"blockstore-{n}",
                             daemon=True).start()

    def _serve(self) -> None:
        while True:
            fut, fn, args = self._tasks.get()
            try:
                fut.set_result(fn(*args))
            except Exception as e:  # raised to the caller that waits on it
                fut.set_exception(e)

    def submit(self, fn, *args) -> Future:
        fut = Future()
        self._tasks.put((fut, fn, args))
        return fut


_pool: _Pool | None = None
_spare_runs: list[list[bytearray]] = []  # a closed reader's run buffers, for the next
_lock = threading.Lock()  # guards both


def _shared_pool() -> _Pool:
    """The process's one pool, started at its first use."""
    global _pool
    with _lock:
        if _pool is None:
            _pool = _Pool(_pool_width())
        return _pool


def _after_fork() -> None:  # a forked child has none of the parent's threads
    global _pool, _lock
    _pool, _lock = None, threading.Lock()


if hasattr(os, "register_at_fork"):
    os.register_at_fork(after_in_child=_after_fork)


def _finish(jobs: list[Future]) -> BaseException | None:
    """Wait for every job; the first one's error, if any failed."""
    errors = [j.exception() for j in jobs]  # each call waits for its job
    return next((e for e in errors if e is not None), None)


@dataclass(frozen=True)
class BlockManifest:
    """The metadata-store entry for one payload (paper: the image manifest).

    The manifest is what a worker downloads first (provisioning protocol
    step 2): it is tiny, and from it the worker derives exactly which blocks
    any byte range needs.
    """

    block_size: int
    n_blocks: int
    raw_size: int
    offsets: tuple[int, ...]  # n_blocks + 1 entries into the data area
    codec: str = field(default="zstd", compare=False)

    def compressed_size(self) -> int:
        return self.offsets[-1]

    def block_range_for(self, offset: int, length: int) -> tuple[int, int]:
        """[first, last] block indices covering raw range [offset, offset+length)."""
        if length <= 0:
            return (0, -1)
        first = offset // self.block_size
        last = (offset + length - 1) // self.block_size
        return first, min(last, self.n_blocks - 1)

    def block_compressed_size(self, i: int) -> int:
        return self.offsets[i + 1] - self.offsets[i]

    def block_raw_size(self, i: int) -> int:
        if i < self.n_blocks - 1:
            return self.block_size
        rem = self.raw_size - self.block_size * (self.n_blocks - 1)
        return rem

    def to_dict(self) -> dict:
        return {
            "block_size": self.block_size,
            "n_blocks": self.n_blocks,
            "raw_size": self.raw_size,
            "offsets": list(self.offsets),
            "codec": self.codec,
        }

    @classmethod
    def from_dict(cls, d: dict) -> "BlockManifest":
        return cls(
            d["block_size"],
            d["n_blocks"],
            d["raw_size"],
            tuple(d["offsets"]),
            d.get("codec", "zstd"),
        )


def write_blockstore(
    payload: bytes,
    path: str,
    *,
    block_size: int = DEFAULT_BLOCK_SIZE,
    level: int | None = None,
    codec: str | None = None,
) -> BlockManifest:
    """Convert ``payload`` into the I/O-efficient format (gateway's job, §3.1).

    ``codec`` defaults to zstd when available, else the stdlib zlib fallback.
    ``level=None`` uses the selected codec's own default (zstd 3, zlib 6) —
    a pinned numeric level applies verbatim to whichever codec is chosen.
    """
    codec = codec or default_codec()
    cctx = _make_codec(codec, level)
    n_blocks = max(1, -(-len(payload) // block_size))
    blocks = [
        cctx.compress(payload[i * block_size : (i + 1) * block_size])
        for i in range(n_blocks)
    ]
    offsets = [0]
    for b in blocks:
        offsets.append(offsets[-1] + len(b))
    manifest = BlockManifest(block_size, n_blocks, len(payload), tuple(offsets), codec)
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        f.write(
            _HEADER.pack(
                MAGIC, _VERSION_BY_CODEC[codec], block_size, n_blocks, len(payload)
            )
        )
        f.write(struct.pack(f"<{n_blocks + 1}Q", *offsets))
        for b in blocks:
            f.write(b)
    os.replace(tmp, path)  # atomic publish (crash-safe checkpointing relies on it)
    return manifest


def read_manifest(path: str) -> BlockManifest:
    with open(path, "rb") as f:
        magic, version, block_size, n_blocks, raw_size = _HEADER.unpack(
            f.read(_HEADER.size)
        )
        if magic != MAGIC:
            raise ValueError(f"{path}: not a blockstore file (magic {magic:#x})")
        if version not in _CODEC_BY_VERSION:
            raise ValueError(f"{path}: unsupported version {version}")
        offsets = struct.unpack(f"<{n_blocks + 1}Q", f.read(8 * (n_blocks + 1)))
    return BlockManifest(
        block_size, n_blocks, raw_size, tuple(offsets), _CODEC_BY_VERSION[version]
    )


@dataclass
class ReadStats:
    """Accounting for the read-amplification analysis (paper Fig. 20)."""

    useful_bytes: int = 0  # bytes the caller asked for
    fetched_compressed: int = 0  # compressed bytes moved over the "network"
    fetched_raw: int = 0  # raw bytes materialized after decompression
    blocks_fetched: int = 0

    def amplification(self) -> float:
        return self.fetched_raw / self.useful_bytes if self.useful_bytes else 0.0


class BlockReader:
    """On-demand reader over a blockstore file with a block cache.

    Models the FaaSNet worker's lazy fetch: a range read touches only the
    covering blocks; previously fetched blocks are served from cache (the
    worker's local storage) without re-counting network bytes.

    I/O discipline: one persistent file handle for the reader's lifetime
    (use :meth:`close` or the context-manager protocol), and
    :meth:`read_range` coalesces runs of contiguous uncached blocks into a
    single seek+read of at most ``MAX_RUN_BYTES`` compressed bytes, into
    one of two buffers the reader reuses — the compressed blocks are
    back-to-back on disk, so a cold sequential range costs one syscall per
    run instead of one per block. A closed reader hands its two buffers to
    the next reader of the process, so a restore allocates none. ``stats`` accounting is unchanged: the same
    per-block useful/fetched byte and block counts as the
    one-read-per-block implementation, each block counted once.

    :meth:`read_range_into` assembles a range in place: a block the range
    covers whole is decompressed straight into the caller's buffer (a
    restored leaf's own host array) and not kept; a block it covers in
    part is kept in the cache, since the neighbouring range needs it, and
    its bytes are copied into place. So a restore holds no second copy of
    the payload, and allocates no block-sized buffers that the allocator
    may or may not hand back between restores. :meth:`read_range` is the
    same routine into a fresh buffer, returned as ``bytes``.

    A run's whole blocks are decompressed in parallel: they are cut into
    contiguous slices, one for each thread of a pool the process shares
    (daemon threads, as many as the CPUs the process may use by affinity
    and cgroup quota, at most ``_POOL_MAX``, the calling thread included),
    and each thread writes its blocks into their places with its own
    decompression context (``zstandard`` and ``zlib`` release the GIL).
    The calling thread reads the next run, into its other buffer, before
    it decompresses its own slice and waits for the pool. A run with fewer than two whole blocks stays on the calling
    thread. Counts, the cache and the spans are kept on the calling
    thread: ``blockstore.decompress`` is its wall time over a run, with
    ``pool_blocks``, the run's blocks decompressed off it. A worker's
    error, such as a corrupt block, is raised from the read.
    """

    def __init__(self, path: str, manifest: BlockManifest | None = None) -> None:
        self.path = path
        self.manifest = manifest or read_manifest(path)
        self._data_start = _HEADER.size + 8 * (self.manifest.n_blocks + 1)
        self._cache: dict[int, bytes] = {}
        self._fetched: set[int] = set()  # blocks counted in ``stats``
        with _lock:  # compressed runs, read into each in turn
            self._run_bufs = _spare_runs.pop() if _spare_runs else [bytearray(), bytearray()]
        self._codec = _make_codec(self.manifest.codec)  # decompress side: level moot
        self.stats = ReadStats()
        self._f = open(path, "rb")
        self.file_reads = 0  # seek+read syscall pairs issued (coalescing telemetry)

    # -- lifecycle -------------------------------------------------------
    def close(self) -> None:
        if self._f is not None:
            self._f.close()
            self._f = None
            with _lock:  # the next reader's, so a restore allocates none
                if not _spare_runs:
                    _spare_runs.append(self._run_bufs)
            self._run_bufs = None

    def __enter__(self) -> "BlockReader":
        return self

    def __exit__(self, *exc) -> None:
        self.close()

    def __del__(self) -> None:  # pragma: no cover - GC safety net
        try:
            self.close()
        except Exception:
            pass

    def _read_at(self, first: int, last: int, buf: int = 0) -> memoryview:
        """Compressed blocks [first, last], read into reused buffer ``buf``."""
        if self._f is None:
            raise ValueError(f"BlockReader for {self.path} is closed")
        m = self.manifest
        size = m.offsets[last + 1] - m.offsets[first]
        if len(self._run_bufs[buf]) < size:
            self._run_bufs[buf] = bytearray(size)
        view = memoryview(self._run_bufs[buf])[:size]
        with span("blockstore.read", bytes=size, blocks=last + 1 - first):
            self._f.seek(self._data_start + m.offsets[first])
            self.file_reads += 1
            if self._f.readinto(view) != size:
                raise ValueError(f"{self.path}: truncated block data")
        return view

    # -- block-level -----------------------------------------------------
    def fetch_block_compressed(self, i: int) -> bytes:
        """Raw compressed block i — the unit streamed down FT edges."""
        return bytes(self._read_at(i, i))

    def get_block(self, i: int) -> bytes:
        if i not in self._cache:
            self._fetch_run(i, i)
        return self._cache[i]

    def _runs(self, first: int, last: int) -> list[tuple[int, int]]:
        """The contiguous runs of uncached blocks in [first, last], each of
        at most ``MAX_RUN_BYTES`` compressed (or one block)."""
        m = self.manifest
        runs, i = [], first
        while i <= last:
            if i in self._cache:
                i += 1
                continue
            j = i
            while (j + 1 <= last and (j + 1) not in self._cache
                   and m.offsets[j + 2] - m.offsets[i] <= MAX_RUN_BYTES):
                j += 1
            runs.append((i, j))
            i = j + 1
        return runs

    def _fetch_run(self, first: int, last: int,
                   into: tuple[int, memoryview] | None = None) -> None:
        """Fetch uncached blocks [first, last] with one read per run (see
        :meth:`_runs`). A block is decompressed into its place in ``into``
        = (raw offset, buffer) when the buffer holds it whole, on the pool
        when the run has two such blocks or more; else into the cache."""
        m = self.manifest
        runs = self._runs(first, last)
        comp = self._read_at(*runs[0]) if runs else None
        for n, (i, j) in enumerate(runs):
            base = m.offsets[i]
            whole, parts = [], []
            for k in range(i, j + 1):
                block = comp[m.offsets[k] - base : m.offsets[k + 1] - base]
                lo = k * m.block_size - into[0] if into is not None else -1
                hi = lo + m.block_raw_size(k)
                if 0 <= lo and hi <= len(into[1]):
                    whole.append((k, block, into[1][lo:hi]))
                else:
                    parts.append((k, block))
            mine, jobs = whole, []
            if len(whole) >= 2:  # contiguous slices, the calling thread's last
                pool = _shared_pool()
                w = min(pool.width, len(whole))
                cuts = [len(whole) * s // w for s in range(w + 1)]
                jobs = [pool.submit(_decompress_blocks, m.codec, self.path, whole[a:b])
                        for a, b in zip(cuts, cuts[1:-1])]
                mine = whole[cuts[-2]:]
            try:
                # the next run is read while the pool works on this one
                nxt = self._read_at(*runs[n + 1], (n + 1) % 2) if n + 1 < len(runs) else None
                with span("blockstore.decompress", blocks=j + 1 - i,
                          pool_blocks=len(whole) - len(mine)) as sp:
                    _decompress_blocks(m.codec, self.path, mine)
                    for k, block in parts:
                        self._cache[k] = self._decompress(k, block)
                    err = _finish(jobs)
                    if err is not None:
                        raise err
                    sp.set(raw_bytes=sum(m.block_raw_size(k) for k in range(i, j + 1)))
            finally:  # on an error too: no worker writes on once the read returns
                _finish(jobs)
            for k in range(i, j + 1):
                if k not in self._fetched:
                    self._fetched.add(k)
                    self.stats.blocks_fetched += 1
                    self.stats.fetched_compressed += m.block_compressed_size(k)
                    self.stats.fetched_raw += m.block_raw_size(k)
            comp = nxt

    def _decompress(self, i: int, comp) -> bytes:
        """Block ``i`` from its compressed bytes ``comp``, for the cache."""
        try:
            return self._codec.decompress(comp, self.manifest.block_raw_size(i))
        except _CODEC_ERRORS as e:
            raise ValueError(f"{self.path}: block {i}: {e}") from e

    # -- range-level (on-demand I/O) --------------------------------------
    def _check_range(self, offset: int, length: int) -> None:
        m = self.manifest
        if length < 0:
            raise ValueError(f"negative read length {length}")
        if offset < 0 or offset + length > m.raw_size:
            raise ValueError(
                f"range [{offset}, {offset + length}) outside payload of {m.raw_size}"
            )

    def read_range(self, offset: int, length: int) -> bytes:
        self._check_range(offset, length)
        out = bytearray(length)
        self.read_range_into(offset, length, out)
        return bytes(out)

    def read_range_into(self, offset: int, length: int, out) -> None:
        """Raw range [offset, offset+length) into ``out``, a writable
        C-contiguous buffer of exactly ``length`` bytes: blocks it covers
        whole are decompressed into their place, the bytes of the others
        copied there from the cache."""
        self._check_range(offset, length)
        dst = memoryview(out).cast("B")
        if dst.readonly or dst.nbytes != length:
            raise ValueError(f"need a writable buffer of {length} bytes")
        m = self.manifest
        self.stats.useful_bytes += length
        first, last = m.block_range_for(offset, length)
        cached = sum(1 for i in range(first, last + 1) if i in self._cache)
        with span("blockstore.read_range", bytes=length, blocks=last + 1 - first,
                  blocks_cached=cached):
            if first <= last:
                self._fetch_run(first, last, (offset, dst))
            pos = 0
            for i in range(first, last + 1):
                lo = max(0, offset - i * m.block_size)
                hi = min(m.block_raw_size(i), offset + length - i * m.block_size)
                if i in self._cache:  # else decompressed into place above
                    dst[pos : pos + hi - lo] = memoryview(self._cache[i])[lo:hi]
                pos += hi - lo

    def read_all(self) -> bytes:
        return self.read_range(0, self.manifest.raw_size)
