"""Out-of-core ingest benchmark: streamed partitioning under a memory cap.

The point of ``repro.rsp.ingest`` is that an RSP dataset can be *built* from
a corpus that never fits in RAM -- the premise of the paper's "generated in
advance" blocks.  Two measurements:

1. **Capped streaming ingest** -- a record-batch generator (the corpus never
   exists whole anywhere, not even on disk) streams through
   ``rsp.from_source`` into a stored RSP.  ``tracemalloc`` meters the peak
   allocated working set (numpy buffers are traced; the memmapped block
   files are exactly the out-of-core part, backed by disk); the cap is
   enforced -- ``--smoke`` exits non-zero if the peak exceeds it -- and the
   corpus is several times larger than it.

2. **Sketch-only equivalence** -- the finished store answers
   ``query(["mean", "count"])`` from its partition-time sketches (zero
   block reads, witnessed by the executor's fetch counter) and the answer
   must match a full-scan pass over a regenerated copy of the stream: the
   single ingest pass loses nothing.

Usage::

    PYTHONPATH=src python -m benchmarks.ingest_bench            # full sizes
    PYTHONPATH=src python -m benchmarks.ingest_bench --smoke    # CI gate

``--smoke`` uses small sizes and exits non-zero unless (a) peak traced
memory stays under the cap, (b) the corpus is >= 4x the cap, and (c) the
sketch-only query matches the full-scan answer -- so regressions in the
bounded-memory claim fail loudly.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time
import tracemalloc

import numpy as np

from benchmarks.artifact import write_artifact
from repro import rsp
from repro.runtime import use_compile_cache


def _chunk_stream(num_chunks: int, chunk_records: int, features: int, seed: int = 9):
    """Deterministic record-batch generator; rebuildable for the verify scan."""
    for c in range(num_chunks):
        rng = np.random.default_rng(np.random.SeedSequence([seed, c]))
        yield rng.normal(loc=1.5, scale=2.0, size=(chunk_records, features)).astype(
            np.float32
        )


def _full_scan_truth(num_chunks: int, chunk_records: int, features: int):
    """Corpus mean/count from a plain streaming accumulation (the answer the
    store's sketches must reproduce)."""
    total = np.zeros(features, dtype=np.float64)
    count = 0
    for chunk in _chunk_stream(num_chunks, chunk_records, features):
        total += chunk.sum(axis=0, dtype=np.float64)
        count += chunk.shape[0]
    return total / count, count


def bench_capped_ingest(
    *,
    blocks: int,
    block_records: int,
    features: int,
    chunk_records: int,
    cap_bytes: int,
) -> dict[str, float]:
    n = blocks * block_records
    corpus_bytes = n * features * 4
    num_chunks = n // chunk_records
    with tempfile.TemporaryDirectory() as tmp:
        out = os.path.join(tmp, "corpus.rsp")
        source = rsp.IterChunkSource(
            _chunk_stream(num_chunks, chunk_records, features),
            num_records=n,
            record_shape=(features,),
            dtype=np.float32,
        )
        tracemalloc.start()
        tracemalloc.reset_peak()
        t0 = time.perf_counter()
        ds = rsp.from_source(source, blocks=blocks, out=out, seed=1,
                             chunk_records=chunk_records)
        elapsed = time.perf_counter() - t0
        _, peak = tracemalloc.get_traced_memory()
        tracemalloc.stop()

        assert ds.store is not None and ds.has_summaries
        before = ds.executor.stats()
        res = ds.query(["mean", "count"])
        fetched = (ds.executor.stats() - before).blocks_fetched
        truth_mean, truth_count = _full_scan_truth(num_chunks, chunk_records, features)
        mean_err = float(np.max(np.abs(res["mean"].estimate - truth_mean)))
        count_err = abs(float(res["count"].estimate) - truth_count)
        ds.store.load_block(0, mmap=False, verify=True)  # checksums are real
        ds.close()
    return {
        "corpus_bytes": corpus_bytes,
        "cap_bytes": cap_bytes,
        "peak_bytes": float(peak),
        "records_per_s": n / elapsed,
        "sketch_mean_err": mean_err,
        "sketch_count_err": count_err,
        "sketch_blocks_fetched": float(fetched),
        "from_sketches": float(res.from_sketches),
    }


# the cap covers the scatter working set plus the per-block sketch-suite
# state (KLL + KMV columns; O(K * F * k), independent of corpus size); the
# corpus must still be >= 4x the cap so the out-of-core claim stays real
SMOKE_SIZES = dict(blocks=16, block_records=24576, features=32,
                   chunk_records=2048, cap_bytes=12 << 20)
FULL_SIZES = dict(blocks=32, block_records=65536, features=32,
                  chunk_records=16384, cap_bytes=32 << 20)


def _rows(r: dict[str, float]) -> list[tuple[str, float, str]]:
    ratio = r["corpus_bytes"] / r["cap_bytes"]
    return [
        (
            "ingest_capped_stream",
            r["records_per_s"],
            f"records_per_s={r['records_per_s']:,.0f} "
            f"corpus_mb={r['corpus_bytes'] / 2**20:.0f} "
            f"cap_mb={r['cap_bytes'] / 2**20:.0f} "
            f"peak_mb={r['peak_bytes'] / 2**20:.1f} ratio={ratio:.1f}x",
        ),
        (
            "ingest_sketch_equivalence",
            r["sketch_mean_err"],
            f"mean_err={r['sketch_mean_err']:.2e} count_err={r['sketch_count_err']:.0f} "
            f"blocks_fetched={r['sketch_blocks_fetched']:.0f} "
            f"from_sketches={bool(r['from_sketches'])}",
        ),
    ]


def ingest_rows(smoke: bool = False) -> list[tuple[str, float, str]]:
    """``benchmarks.run``-style rows: (name, value, derived)."""
    return _rows(bench_capped_ingest(**(SMOKE_SIZES if smoke else FULL_SIZES)))


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="small sizes + hard pass/fail gate")
    args = ap.parse_args()

    r = bench_capped_ingest(**(SMOKE_SIZES if args.smoke else FULL_SIZES))
    ratio = r["corpus_bytes"] / r["cap_bytes"]
    rows = _rows(r)
    print("name,value,derived")
    for name, value, derived in rows:
        print(f"{name},{value:.1f},{derived}")
    # the standalone entry point must leave the same machine-readable
    # artifact benchmarks.run would (CI uploads BENCH_*.json; this one was
    # silently missing)
    path = write_artifact("ingest", rows, extra={"smoke": args.smoke, "raw": r})

    if args.smoke:
        ok = True
        if not os.path.isfile(path) or os.path.getsize(path) == 0:
            print(f"SMOKE FAIL: artifact {path} was not written", file=sys.stderr)
            ok = False
        if r["peak_bytes"] > r["cap_bytes"]:
            print(
                f"SMOKE FAIL: ingest peak {r['peak_bytes'] / 2**20:.1f} MB exceeds"
                f" the {r['cap_bytes'] / 2**20:.0f} MB memory cap",
                file=sys.stderr,
            )
            ok = False
        if ratio < 4.0:
            print(f"SMOKE FAIL: corpus only {ratio:.1f}x the cap (< 4x)", file=sys.stderr)
            ok = False
        if not bool(r["from_sketches"]) or r["sketch_blocks_fetched"] != 0:
            print("SMOKE FAIL: sketch query read block data", file=sys.stderr)
            ok = False
        if r["sketch_mean_err"] > 1e-5 or r["sketch_count_err"] != 0:
            print(
                f"SMOKE FAIL: sketch answer diverges from full scan"
                f" (mean_err={r['sketch_mean_err']:.2e},"
                f" count_err={r['sketch_count_err']:.0f})",
                file=sys.stderr,
            )
            ok = False
        if not ok:
            sys.exit(1)
        print(
            f"SMOKE OK: {ratio:.1f}x-cap corpus streamed at peak"
            f" {r['peak_bytes'] / 2**20:.1f} MB; sketch query == full scan"
            f" with 0 block reads"
        )


if __name__ == "__main__":
    main()
