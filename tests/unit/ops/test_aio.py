"""Native async-I/O tests (reference ``tests/unit/ops/aio/test_aio.py``)."""

import os

import numpy as np
import pytest

from deepspeed_tpu.ops.aio.aio_handle import AsyncIOHandle, aio_available

pytestmark = pytest.mark.skipif(not aio_available(),
                                reason="native aio op failed to build")


def test_write_read_roundtrip(tmp_path):
    h = AsyncIOHandle(thread_count=2)
    data = np.random.default_rng(0).standard_normal(100_000).astype(np.float32)
    path = str(tmp_path / "buf.bin")
    h.sync_pwrite(data, path)
    out = np.empty_like(data)
    h.sync_pread(out, path)
    np.testing.assert_array_equal(out, data)
    h.close()


def test_async_batch_overlap(tmp_path):
    """Many in-flight ops across files complete under one wait()."""
    h = AsyncIOHandle(thread_count=4)
    bufs = [np.full(50_000, float(i), np.float32) for i in range(8)]
    for i, b in enumerate(bufs):
        h.async_pwrite(b, str(tmp_path / f"f{i}.bin"))
    h.wait()
    outs = [np.empty(50_000, np.float32) for _ in range(8)]
    for i, o in enumerate(outs):
        h.async_pread(o, str(tmp_path / f"f{i}.bin"))
    h.wait()
    for i, o in enumerate(outs):
        np.testing.assert_array_equal(o, float(i))
    h.close()


def test_offset_io(tmp_path):
    h = AsyncIOHandle()
    base = np.arange(1000, dtype=np.float32)
    path = str(tmp_path / "off.bin")
    h.sync_pwrite(base, path)
    tail = np.empty(500, np.float32)
    h.sync_pread(tail, path, offset=500 * 4)
    np.testing.assert_array_equal(tail, base[500:])
    # partial overwrite at offset
    patch = np.full(100, -1.0, np.float32)
    h.sync_pwrite(patch, path, offset=200 * 4)
    full = np.empty(1000, np.float32)
    h.sync_pread(full, path)
    np.testing.assert_array_equal(full[200:300], -1.0)
    np.testing.assert_array_equal(full[:200], base[:200])
    h.close()


def test_read_error_raises(tmp_path):
    h = AsyncIOHandle()
    buf = np.empty(10, np.float32)
    with pytest.raises(OSError):
        h.sync_pread(buf, str(tmp_path / "missing.bin"))
    h.close()


# ------------------------------------------------------------------ O_DIRECT path
class TestODirect:
    """O_DIRECT aio: aligned-buffer helpers, correctness through
    the direct path (with per-filesystem buffered fallback), and a sequential-
    throughput microbench documenting direct vs buffered."""

    def test_aligned_array_contract(self):
        from deepspeed_tpu.ops.aio.aio_handle import (O_DIRECT_ALIGN, aligned_array,
                                                      padded_len)
        a = aligned_array(10_000, np.float32)
        assert a.ctypes.data % O_DIRECT_ALIGN == 0
        assert a.nbytes % O_DIRECT_ALIGN == 0 and a.nbytes >= 10_000
        assert padded_len(1000, 4) * 4 % O_DIRECT_ALIGN == 0
        assert padded_len(1024, 4) == 1024   # already aligned: unchanged

    def test_direct_roundtrip(self, tmp_path):
        from deepspeed_tpu.ops.aio.aio_handle import (AsyncIOHandle, aio_available,
                                                      aligned_array)
        if not aio_available():
            pytest.skip("native aio unavailable")
        h = AsyncIOHandle(thread_count=2, o_direct=True)
        n = 1 << 20
        src = aligned_array(n, np.uint8)
        src[:] = np.arange(n, dtype=np.uint64).view(np.uint8)[:n]
        dst = aligned_array(n, np.uint8)
        f = str(tmp_path / "direct.bin")
        h.sync_pwrite(src, f)
        h.sync_pread(dst, f)
        np.testing.assert_array_equal(dst, src)
        h.close()

    def test_sequential_throughput_floor(self, tmp_path):
        """Direct-vs-buffered sequential write+read microbench. Asserts both modes
        move data correctly and the direct path achieves a sane fraction of the
        buffered path (page cache makes buffered look fast on small files; the
        floor guards against a pathologically broken O_DIRECT configuration)."""
        import time
        from deepspeed_tpu.ops.aio.aio_handle import (AsyncIOHandle, aio_available,
                                                      aligned_array)
        if not aio_available():
            pytest.skip("native aio unavailable")
        n = 64 << 20   # 64 MiB
        buf = aligned_array(n, np.uint8)
        buf[:] = 7

        def run(o_direct):
            h = AsyncIOHandle(thread_count=2, block_size=1 << 20,
                              o_direct=o_direct)
            f = str(tmp_path / f"bench_{o_direct}.bin")
            t0 = time.perf_counter()
            h.sync_pwrite(buf, f)
            h.sync_pread(buf, f)
            dt = time.perf_counter() - t0
            h.close()
            return 2 * n / dt / 2**20   # MiB/s

        buffered = run(False)
        direct = run(True)
        print(f"\naio sequential: buffered {buffered:.0f} MiB/s, "
              f"direct {direct:.0f} MiB/s")
        # absolute floor, not a buffered-relative one: the buffered baseline never
        # leaves the page cache on a 64 MiB file, while direct hits media — a ratio
        # assert would flake on slow disks. 10 MiB/s only guards against a
        # pathologically broken O_DIRECT configuration.
        assert direct > 10, (direct, buffered)
