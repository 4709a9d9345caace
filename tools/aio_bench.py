"""NVMe/disk I/O sweep over the C++ aio engine — the reference's
``csrc/aio/py_test/aio_bench_perf_sweep.py`` role: measure read/write
bandwidth across (thread count, block size, O_DIRECT) so ZeRO-Infinity's
swap config (``aio`` block in the JSON) can be tuned for the host.

Prints one JSON line per configuration plus a ``best`` summary whose
fields are exactly the config keys the swap path consumes
(``aio: {thread_count, block_size}``). Pure host work: no chip is touched.

Run: python tools/aio_bench.py   [AIO_DIR=/tmp AIO_MB=256 AIO_THREADS=1,4,8]
"""
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deepspeed_tpu.ops.aio import AsyncIOHandle

TOTAL_MB = int(os.environ.get("AIO_MB", "256"))
THREADS = [int(t) for t in os.environ.get("AIO_THREADS", "1,4,8").split(",")]
BLOCK_MB = [int(b) for b in os.environ.get("AIO_BLOCKS_MB", "1,8,32").split(",")]
DIRECT = [False, True]


def run_config(dirname, n_threads, block_mb, direct, data):
    n_blocks = max(1, TOTAL_MB // block_mb)
    # one distinct VIEW per in-flight op into the pre-generated data pool:
    # shared OUTPUT buffers would race concurrent reads (views are fine for
    # writes — read-only during I/O)
    bs = block_mb << 20
    blocks = [data[i * bs:(i + 1) * bs] for i in range(n_blocks)]
    paths = [os.path.join(dirname, f"aio_{i}.bin") for i in range(n_blocks)]
    h = AsyncIOHandle(n_threads=n_threads, use_direct=direct)
    fell_back = False
    try:
        t0 = time.perf_counter()
        for blk, p in zip(blocks, paths):
            h.pwrite(blk, p)
        errs = h.wait()
        dt_w = time.perf_counter() - t0
        assert errs == 0, f"{errs} write errors"
        out = [np.empty(block_mb << 20, np.uint8) for _ in range(n_blocks)]
        t0 = time.perf_counter()
        for buf, p in zip(out, paths):
            h.pread(buf, p)
        errs = h.wait()
        dt_r = time.perf_counter() - t0
        assert errs == 0, f"{errs} read errors"
        # round-trip integrity on a sample block
        assert np.array_equal(out[0], blocks[0]), "read-back mismatch"
        fell_back = direct and h.direct_fallbacks() > 0
    finally:
        h.close()
        for p in paths:
            try:
                os.unlink(p)
            except OSError:
                pass
    total = n_blocks * block_mb
    return total / dt_w, total / dt_r, fell_back


def main():
    base = os.environ.get("AIO_DIR") or tempfile.mkdtemp(prefix="aio_bench_")
    try:
        os.makedirs(base, exist_ok=True)
        probe = os.path.join(base, ".aio_probe")
        with open(probe, "wb") as f:
            f.write(b"x")
        os.unlink(probe)
    except OSError as e:
        print(json.dumps({"error": f"AIO_DIR {base!r} not writable: {e}"}), flush=True)
        return 1
    data = np.random.default_rng(0).integers(0, 255, TOTAL_MB << 20, dtype=np.uint8)
    # best is chosen among O_DIRECT configs: buffered numbers measure the
    # page cache, not the disk (no fsync; reads hit just-written cache) —
    # they print for reference but must not tune the swap config. Only if
    # no O_DIRECT config completed (filesystem refuses it) does the
    # buffered best stand in.
    best = {True: None, False: None}
    try:
        for direct in DIRECT:
            for n_threads in THREADS:
                for block_mb in BLOCK_MB:
                    if block_mb > TOTAL_MB:
                        print(json.dumps({"threads": n_threads, "block_mb": block_mb,
                                          "direct": direct,
                                          "skipped": f"block larger than AIO_MB={TOTAL_MB}"}),
                              flush=True)
                        continue
                    try:
                        w, r, fell_back = run_config(base, n_threads, block_mb, direct, data)
                    except Exception as e:  # keep sweeping past per-config failures
                        print(json.dumps({"threads": n_threads, "block_mb": block_mb,
                                          "direct": direct,
                                          "error": f"{type(e).__name__}: {e}"[:200]}),
                              flush=True)
                        continue
                    line = {"threads": n_threads, "block_mb": block_mb,
                            "direct": direct, "write_MBps": round(w, 1),
                            "read_MBps": round(r, 1)}
                    bucket = direct
                    if fell_back:
                        # the engine silently ran buffered (tmpfs etc.):
                        # these are page-cache numbers, not O_DIRECT ones
                        line["direct_effective"] = False
                        bucket = False
                    print(json.dumps(line), flush=True)
                    score = min(w, r)
                    if best[bucket] is None or score > best[bucket][0]:
                        best[bucket] = (score, {"thread_count": n_threads,
                                                "block_size": block_mb << 20,
                                                "use_direct": bucket})
    finally:
        if not os.environ.get("AIO_DIR"):
            import shutil
            shutil.rmtree(base, ignore_errors=True)
    chosen = best[True] or best[False]
    if chosen is None:
        print(json.dumps({"error": "no configuration completed"}), flush=True)
        return 1
    note = None if best[True] else "O_DIRECT unavailable; buffered (page-cache) numbers"
    line = {"best": chosen[1], "min_MBps": round(chosen[0], 1)}
    if note:
        line["note"] = note
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
