"""Kernel micro-benchmarks: Pallas (interpret) vs jnp reference oracles.

On CPU the interpret-mode numbers are correctness/plumbing benchmarks, not
TPU performance; the TPU-side expectation is derived analytically in
EXPERIMENTS.md (VMEM-resident state removes the HBM round-trips that
dominate the jnp paths).

The plan-kernel rows are *real* CPU performance claims, though: the fused
filter+sketch numpy path must beat the two-pass mask-then-sketch baseline
at equal results, and the autotuned tile must beat (or tie) the retired
hardcoded 128-row tile.  ``--smoke`` enforces both::

    PYTHONPATH=src python -m benchmarks.kernel_bench            # rows only
    PYTHONPATH=src python -m benchmarks.kernel_bench --smoke    # CI gate
"""

from __future__ import annotations

import argparse
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.runtime import interpret_mode, use_compile_cache

Row = tuple  # (name, value, derived[, metrics dict])

# the --smoke gate: fused one-pass filter+sketch vs the two-pass baseline
PLAN_SPEEDUP_GATE = 1.5
# autotuned tile must be within this factor of hardcoded 128 (ties logged)
TILE_TIE_MARGIN = 1.05


def _timeit(fn, repeat=3) -> float:
    fn()
    t0 = time.perf_counter()
    for _ in range(repeat):
        fn()
    return (time.perf_counter() - t0) / repeat * 1e6


def _best_of(fn, repeat=5) -> float:
    """Min-of-N microseconds -- the noise-robust timer the gates use (mean
    timings on a loaded CI host have inflated perf ratios by 5x before)."""
    fn()
    best = float("inf")
    for _ in range(repeat):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def bench_flash_attention() -> list[Row]:
    from repro.kernels.flash_attention import ops as fa
    from repro.kernels.flash_attention.ref import flash_attention_ref

    rows = []
    B, H, Hkv, S, D = 1, 8, 2, 512, 64
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, H, S, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, S, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, S, D), jnp.float32)
    flops = 4 * B * H * S * S * D * 0.5
    us = _timeit(lambda: jax.block_until_ready(
        fa.flash_attention(q, k, v, causal=True, block_q=128, block_k=128)), repeat=1)
    rows.append(("flash_attn_pallas_interp_512", us, f"gflops={flops / (us / 1e6) / 1e9:.2f}"))
    ref = jax.jit(lambda q, k, v: flash_attention_ref(q, k, v, causal=True))
    us = _timeit(lambda: jax.block_until_ready(ref(q, k, v)))
    rows.append(("flash_attn_ref_jnp_512", us, f"gflops={flops / (us / 1e6) / 1e9:.2f}"))
    return rows


def bench_rsp_shuffle() -> list[Row]:
    from repro.kernels.rsp_shuffle import ops as rs

    rows = []
    R, D, T = 65_536, 32, 256
    x = jax.random.normal(jax.random.PRNGKey(0), (R, D), jnp.float32)
    tp, ip = rs.make_permutations(jax.random.PRNGKey(1), R // T, T)
    gb = R * D * 4 / 1e9
    us = _timeit(lambda: jax.block_until_ready(rs.rsp_shuffle(x, tp, ip, tile_rows=T)), repeat=1)
    rows.append(("rsp_shuffle_pallas_interp_64k", us, f"gbps={gb / (us / 1e6):.3f}"))
    gather = jax.jit(lambda x, idx: x[idx])
    idx = jax.random.permutation(jax.random.PRNGKey(2), R)
    us = _timeit(lambda: jax.block_until_ready(gather(x, idx)))
    rows.append(("rsp_shuffle_xla_gather_64k", us, f"gbps={gb / (us / 1e6):.3f}"))
    return rows


def bench_ssd_and_wkv() -> list[Row]:
    from repro.kernels.mamba2_ssd import ops as ssd_ops
    from repro.models.mamba2 import ssd_chunked, ssd_reference
    from repro.kernels.rwkv6_wkv import ops as wkv_ops
    from repro.models.rwkv6 import wkv6_scan

    rows = []
    B, L, H, P, N = 1, 512, 8, 64, 64
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    xbar = jax.random.normal(ks[0], (B, L, H, P))
    dA = -jax.nn.softplus(jax.random.normal(ks[1], (B, L, H)))
    Bm = jax.random.normal(ks[2], (B, L, N))
    Cm = jax.random.normal(ks[3], (B, L, N))
    us = _timeit(lambda: jax.block_until_ready(ssd_ops.ssd(xbar, dA, Bm, Cm, chunk=128)), repeat=1)
    rows.append(("mamba2_ssd_pallas_interp_L512", us, ""))
    ref = jax.jit(lambda *a: ssd_chunked(*a, chunk=128))
    us = _timeit(lambda: jax.block_until_ready(ref(xbar, dA, Bm, Cm)))
    rows.append(("mamba2_ssd_jnp_chunked_L512", us, ""))
    scan = jax.jit(ssd_reference)
    us = _timeit(lambda: jax.block_until_ready(scan(xbar, dA, Bm, Cm)))
    rows.append(("mamba2_ssd_jnp_scan_L512", us, ""))

    C = 64
    r = jax.random.normal(ks[0], (B, L, H, C))
    k2 = jax.random.normal(ks[1], (B, L, H, C))
    v2 = jax.random.normal(ks[2], (B, L, H, C))
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (B, L, H, C)))
    u = jnp.full((H, C), 0.3)
    us = _timeit(lambda: jax.block_until_ready(wkv_ops.wkv6(r, k2, v2, w, u, chunk=16)), repeat=1)
    rows.append(("rwkv6_wkv_pallas_interp_L512", us, ""))
    scan2 = jax.jit(wkv6_scan)
    us = _timeit(lambda: jax.block_until_ready(scan2(r, k2, v2, w, u)))
    rows.append(("rwkv6_wkv_jnp_scan_L512", us, ""))
    return rows


def bench_block_sketch() -> list[Row]:
    from repro.kernels.block_sketch import block_sketch
    from repro.kernels.block_sketch.kernel import block_sketch_pallas

    rows = []
    n, f, bins = 16_384, 16, 128
    x = jax.random.normal(jax.random.PRNGKey(4), (n, f), jnp.float32) * 2.0 + 1.5
    lo = jnp.full((f,), -8.0)
    inv_w = jnp.full((f,), bins / 16.0)
    gb = n * f * 4 / 1e9
    us = _timeit(
        lambda: jax.block_until_ready(
            block_sketch_pallas(
                x, lo, inv_w, bins=bins, tile_rows=512, interpret=interpret_mode()
            )[0]
        ),
        repeat=1,
    )
    rows.append(("block_sketch_pallas_interp_16k", us, f"gbps={gb / (us / 1e6):.3f}"))
    xs = np.asarray(x)
    us = _timeit(lambda: block_sketch(xs, bins=bins, lo=-8.0, hi=8.0, impl="jax"))
    rows.append(("block_sketch_jax_fused_16k", us, f"gbps={gb / (us / 1e6):.3f}"))
    return rows


def _plan_close(got, ref, *, tol: float = 1e-5) -> None:
    """Assert two PlanResults agree: counts exactly, moments to ``tol``
    (the repo-wide fused-kernel parity bar), histogram mass exactly."""
    assert got.rows_selected == ref.rows_selected, (got.rows_selected, ref.rows_selected)
    for g, r in zip(got.sketches, ref.sketches):
        assert g.count == r.count
        np.testing.assert_allclose(g.mean, r.mean, rtol=tol, atol=tol)
        np.testing.assert_allclose(g.m2, r.m2, rtol=1e-4, atol=1e-3)
        np.testing.assert_allclose(g.min, r.min, rtol=tol, atol=tol)
        np.testing.assert_allclose(g.max, r.max, rtol=tol, atol=tol)
        if g.hist is not None:
            assert g.hist.sum() == r.hist.sum()


def plan_bench() -> tuple[list[Row], dict]:
    """Fused plan kernels vs the mask-then-sketch two-pass baseline; returns
    ``(rows, gates)`` where ``gates`` carries everything ``--smoke`` needs."""
    from repro.kernels.plan import QueryPlan, plan_sketch, plan_sketch_ref

    n, f, bins = 200_000, 8, 64
    rng = np.random.default_rng(0)
    x = rng.normal(1.5, 2.0, (n, f)).astype(np.float32)
    plan = QueryPlan(predicates=((0, "gt", 1.0), (3, "lt", 2.5)))
    kw = dict(bins=bins, lo=-8.0, hi=12.0)

    ref = plan_sketch_ref(x, plan, **kw)

    def run_np(tile):
        return plan_sketch(x, plan, impl="np", tile_rows=tile, **kw)

    # equal results first: a speedup over wrong answers is not a speedup
    _plan_close(run_np(None), ref)

    us_ref = _best_of(lambda: plan_sketch_ref(x, plan, **kw))
    tiles = (8192, 16384, 32768, 65536)
    times = {t: _best_of(lambda t=t: run_np(t)) for t in tiles}
    best_tile = min(times, key=times.get)
    us_fused = times[best_tile]
    us_128 = _best_of(lambda: run_np(128))
    speedup = us_ref / us_fused
    tile_ratio = us_128 / us_fused
    winning = {"impl": "np", "tile_rows": best_tile, "us": round(us_fused, 1)}

    # grouped variant (informational: per-class flatnonzero/take overhead
    # makes fused ~parity with ref on CPU; the autotuner picks ref there)
    xg = x.copy()
    xg[:, -1] = rng.integers(0, 8, n)
    gplan = QueryPlan(
        predicates=plan.predicates, group_by=f - 1, num_classes=8
    )
    gref = plan_sketch_ref(xg, gplan, **kw)
    _plan_close(plan_sketch(xg, gplan, impl="np", tile_rows=32768, **kw), gref)
    us_g = _best_of(lambda: plan_sketch(xg, gplan, impl="np", tile_rows=32768, **kw))
    us_g_ref = _best_of(lambda: plan_sketch_ref(xg, gplan, **kw))

    # jax + pallas-interpret parity/plumbing rows (small shape: interpret
    # mode is an emulator, timing it at 200k rows is all noise)
    xs = x[:16_384]
    sref = plan_sketch_ref(xs, plan, **kw)
    _plan_close(plan_sketch(xs, plan, impl="jax", **kw), sref)
    us_jax = _best_of(lambda: plan_sketch(xs, plan, impl="jax", **kw), repeat=3)
    _plan_close(plan_sketch(xs, plan, impl="pallas", tile_rows=512, **kw), sref)
    us_pl = _timeit(
        lambda: plan_sketch(xs, plan, impl="pallas", tile_rows=512, **kw), repeat=1
    )

    rows: list[Row] = [
        (
            "plan_fused_filter_200k",
            us_fused,
            f"speedup={speedup:.2f}x vs two-pass sel={ref.selectivity:.2f}"
            f" tile={best_tile}",
            {"rows_per_s": n / (us_fused / 1e6), "autotune": winning},
        ),
        (
            "plan_twopass_baseline_200k",
            us_ref,
            f"mask-then-sketch ref rows_per_s={n / (us_ref / 1e6):,.0f}",
            {"rows_per_s": n / (us_ref / 1e6)},
        ),
        (
            "plan_tile128_retired_200k",
            us_128,
            f"hardcoded tile128={us_128:.0f}us autotuned={us_fused:.0f}us"
            f" ratio={tile_ratio:.2f}x"
            + (" (tie)" if tile_ratio <= TILE_TIE_MARGIN else ""),
            {"rows_per_s": n / (us_128 / 1e6), "autotune": winning},
        ),
        (
            "plan_fused_grouped_200k",
            us_g,
            f"8-class grouped ratio={us_g_ref / us_g:.2f}x vs two-pass",
            {"rows_per_s": n / (us_g / 1e6)},
        ),
        (
            "plan_fused_jax_16k",
            us_jax,
            f"rows_per_s={len(xs) / (us_jax / 1e6):,.0f}",
            {"rows_per_s": len(xs) / (us_jax / 1e6)},
        ),
        ("plan_pallas_interp_16k", us_pl, "interpret-mode plumbing number", {}),
    ]
    gates = {
        "plan_speedup": speedup,
        "plan_speedup_gate": PLAN_SPEEDUP_GATE,
        "tile128_over_autotuned": tile_ratio,
        "tile_tie_margin": TILE_TIE_MARGIN,
        "tile_tie": bool(tile_ratio <= TILE_TIE_MARGIN),
        "winning_config": winning,
        "parity": "checked (1e-5 moments, exact counts/hist mass)",
    }
    return rows, gates


def bench_plan_kernels() -> list[Row]:
    return plan_bench()[0]


ALL_KERNELS = [
    bench_flash_attention,
    bench_rsp_shuffle,
    bench_ssd_and_wkv,
    bench_block_sketch,
    bench_plan_kernels,
]


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--smoke", action="store_true",
        help="plan-kernel rows only + hard pass/fail perf gates",
    )
    args = ap.parse_args()
    from benchmarks.artifact import write_artifact

    if args.smoke:
        rows, gates = plan_bench()
    else:
        rows = [r for fn in ALL_KERNELS for r in fn()]
        gates = None
    print("name,us_per_call,derived")
    for row in rows:
        print(f"{row[0]},{row[1]:.1f},{row[2]}")
    extra = {"smoke": args.smoke}
    if gates is not None:
        extra["gates"] = gates
    path = write_artifact("kernels", rows, extra=extra)
    print(f"wrote {path}")

    if args.smoke:
        failures = []
        if gates["plan_speedup"] < PLAN_SPEEDUP_GATE:
            failures.append(
                f"fused filter kernel only {gates['plan_speedup']:.2f}x the"
                f" two-pass baseline (< {PLAN_SPEEDUP_GATE}x)"
            )
        if gates["tile128_over_autotuned"] < 1.0 / TILE_TIE_MARGIN:
            failures.append(
                f"autotuned tile {gates['winning_config']['tile_rows']} is"
                f" slower than hardcoded 128"
                f" (ratio {gates['tile128_over_autotuned']:.2f})"
            )
        for msg in failures:
            print(f"SMOKE FAIL: {msg}", file=sys.stderr)
        if failures:
            sys.exit(1)
        tie = " (tie)" if gates["tile_tie"] else ""
        print(
            f"SMOKE OK: fused filter {gates['plan_speedup']:.2f}x >="
            f" {PLAN_SPEEDUP_GATE}x two-pass at equal results; autotuned"
            f" tile {gates['winning_config']['tile_rows']} vs hardcoded 128:"
            f" {gates['tile128_over_autotuned']:.2f}x{tie}"
        )


if __name__ == "__main__":
    main()
