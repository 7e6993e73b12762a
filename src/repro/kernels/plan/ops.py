"""Plan-compiled fused query kernels: compile cache + impl dispatcher.

``plan_sketch(block, plan, ...)`` runs one :class:`~repro.kernels.plan.plan.
QueryPlan` (predicates + projection + optional group-by) over one block in a
single data pass and returns a :class:`~repro.kernels.plan.ref.PlanResult`.
Four equivalent implementations (1e-5 moment parity; histograms carry the
standing bin-edge caveat):

* ``impl="ref"``    -- mask-then-sketch numpy oracle (two passes; the
  baseline the fused paths are benchmarked against).
* ``impl="np"``     -- cache-blocked fused numpy: each row tile is masked,
  projected, moment-folded (f64 accumulators) and histogrammed while hot in
  cache; the fastest CPU path.
* ``impl="jax"``    -- one jit'd fused pass (masked reductions + scatter
  histogram); the accelerator path.
* ``impl="pallas"`` -- the row-tiled TPU kernel (``plan.kernel``): rows
  failing a predicate are masked inside the same VMEM pass as the Chan
  moment fold and histogram scatter.  Compiled on a TPU, interpreted
  elsewhere (:func:`repro.runtime.interpret_mode`).

``plan_totals(block, typed_plan)`` is the same for a typed table's int32
blocks (:class:`~repro.kernels.plan.plan.TypedPlan`): exact predicates on
the codes, expression measures decoded to float32 and summed per group, in
the same four implementations; its ``impl="auto"`` is untuned (jax on
accelerators, fused numpy on CPU).

Kernels are **compiled per plan**: :func:`compile_plan` closes over the
plan's predicates/columns/groups as constants and memoizes on
``(plan.key(), features, bins, impl, tile)`` -- re-running a plan hits the
cache, changing any predicate misses.  ``impl="auto"`` consults the shared
measured autotuner (:mod:`repro.kernels.autotune`) for the winning
(impl, tile) on this machine; with ``REPRO_AUTOTUNE=off`` it pins the
deterministic default (fused numpy @ ``16384`` rows on CPU, jax on
accelerators).
"""

from __future__ import annotations

import threading
import time
from typing import Callable

import numpy as np

from repro import obs
from repro.kernels import autotune
from repro.kernels.autotune import Candidate
from repro.kernels.block_sketch.ref import BlockSketch, _grid
from repro.kernels.plan.plan import QueryPlan, TypedPlan, decode_measures, group_keys
from repro.kernels.plan.ref import (
    PlanResult,
    PlanTotals,
    plan_sketch_ref,
    plan_totals_ref,
    totals_of,
)
from repro.runtime import count_kernel_run, interpret_mode, to_device, to_host

IMPLS = ("auto", "ref", "np", "jax", "pallas")

NP_TILES = (8192, 16384, 32768, 65536)
# 1024-row tiles of a filtered or grouped plan overflow v5e's scoped VMEM
# at F=29, bins=64 (the 3-D histogram one-hot is [T, F, bins])
PALLAS_TILES = (128, 256, 512)
DEFAULT_NP_TILE = 16384  # the pinned REPRO_AUTOTUNE=off choice on CPU
# the typed pallas fold's tile: it keeps no histogram, so larger than the
# float plan's (4096-row tiles of a 16-column block overflow v5e's scoped VMEM)
TYPED_PALLAS_TILE = 1024

_CACHE: dict[tuple, Callable] = {}
_CACHE_LOCK = threading.Lock()
_HITS = 0
_MISSES = 0


def cache_info() -> dict:
    """Compile-cache counters: ``hits`` / ``misses`` / ``size``."""
    with _CACHE_LOCK:
        return {"hits": _HITS, "misses": _MISSES, "size": len(_CACHE)}


def cache_clear() -> None:
    global _HITS, _MISSES
    with _CACHE_LOCK:
        _CACHE.clear()
        _HITS = _MISSES = 0


# ---------------------------------------------------------------------------
# Implementations (each factory returns run(x32, glo, ghi) -> PlanResult)
# ---------------------------------------------------------------------------

def _inv_width(lo: np.ndarray, hi: np.ndarray, bins: int) -> np.ndarray:
    """Per-feature ``bins / (hi - lo)``; 0 for a constant feature, whose
    mass then lands in bin 0."""
    width = (hi - lo) / max(bins, 1)
    return np.where(width > 0, 1.0 / np.where(width > 0, width, 1.0), 0.0)


def _result(plan, fp, bins, glo, ghi, *, nsel, n, cnt, mean, m2, mn, mx, hist):
    """Assemble numpy per-group stats into a PlanResult."""
    sketches = []
    for g in range(plan.groups):
        sketches.append(
            BlockSketch(
                count=float(cnt[g]),
                mean=np.asarray(mean[g], np.float64),
                m2=np.maximum(np.asarray(m2[g], np.float64), 0.0),
                min=np.asarray(mn[g], np.float64),
                max=np.asarray(mx[g], np.float64),
                hist=None if bins == 0 else np.asarray(hist[g], np.int64),
                lo=glo,
                hi=ghi,
            )
        )
    return PlanResult(rows_total=int(n), rows_selected=int(nsel), sketches=sketches)


def _build_ref(plan, f, bins):
    def run(x, glo, ghi):
        lo = 0.0 if glo is None else glo
        hi = 1.0 if ghi is None else ghi
        return plan_sketch_ref(x, plan, bins=bins, lo=lo, hi=hi)

    return run


_MINMAX_CHUNK = 32


def _minmax_into(a: np.ndarray, mn: np.ndarray, mx: np.ndarray) -> None:
    """Fold columnwise min/max of contiguous ``a`` [k, F] into ``mn``/``mx``.

    numpy's axis-0 reduction over a narrow [k, F] array runs near scalar
    speed; reshaping ``_MINMAX_CHUNK`` rows into one wide row first makes
    the inner reduction SIMD-wide (~12x on 8-feature blocks)."""
    k, f = a.shape
    body = (k // _MINMAX_CHUNK) * _MINMAX_CHUNK
    if body:
        wide = a[:body].reshape(-1, _MINMAX_CHUNK * f)
        np.minimum(mn, wide.min(0).reshape(_MINMAX_CHUNK, f).min(0), out=mn)
        np.maximum(mx, wide.max(0).reshape(_MINMAX_CHUNK, f).max(0), out=mx)
    if body < k:
        np.minimum(mn, a[body:].min(0), out=mn)
        np.maximum(mx, a[body:].max(0), out=mx)


def _build_np(plan, f, bins, tile_rows):
    """Cache-blocked fused numpy path.  Per row tile: predicate mask ->
    ``take`` the survivors -> float32 moment/extrema/histogram work while
    the tile is cache-resident, folded into float64 accumulators across
    tiles (one pass over the block, versus the baseline's mask pass + f64
    per-group sketch passes)."""
    cols = plan.resolve_columns(f)
    project = cols != tuple(range(f))
    cols_arr = np.asarray(cols, np.intp)
    fp = len(cols)
    G = plan.groups
    gcol = None if plan.group_by is None else plan.group_by % f
    preds = plan.predicates
    offs32 = np.arange(fp, dtype=np.int32) * bins

    def run(x, glo, ghi):
        n = x.shape[0]
        cnt = np.zeros(G)
        s = np.zeros((G, fp))
        ss = np.zeros((G, fp))
        mn = np.full((G, fp), np.inf, np.float32)
        mx = np.full((G, fp), -np.inf, np.float32)
        hist = np.zeros(G * fp * bins, np.int64) if bins else None
        if bins:
            lo32 = glo.astype(np.float32)
            invw32 = _inv_width(glo, ghi, bins).astype(np.float32)
        nsel = 0
        for start in range(0, n, tile_rows):
            t = x[start : start + tile_rows]
            if preds:
                m = preds[0].mask(t)
                for p in preds[1:]:
                    m &= p.mask(t)
                idxs = np.flatnonzero(m)
                if idxs.shape[0] == 0:
                    continue
                sel = np.take(t, idxs, axis=0)
            else:
                sel = np.ascontiguousarray(t)
            nsel += sel.shape[0]
            if gcol is not None:
                lab = sel[:, gcol].astype(np.int32)
                ok = (lab >= 0) & (lab < G)
                if not ok.all():
                    sel = sel[ok]
                    lab = lab[ok]
                    if sel.shape[0] == 0:
                        continue
            selp = np.take(sel, cols_arr, axis=1) if project else sel
            sq = selp * selp
            if G == 1:
                cnt[0] += selp.shape[0]
                s[0] += selp.sum(0)   # f32 pairwise per tile, f64 across tiles
                ss[0] += sq.sum(0)
                _minmax_into(selp, mn[0], mx[0])
            else:
                for g in range(G):
                    gi = np.flatnonzero(lab == g)
                    if gi.shape[0] == 0:
                        continue
                    sub = np.take(selp, gi, axis=0)
                    cnt[g] += sub.shape[0]
                    s[g] += sub.sum(0)
                    ss[g] += np.take(sq, gi, axis=0).sum(0)
                    _minmax_into(sub, mn[g], mx[g])
            if bins:
                w = selp - lo32
                w *= invw32
                idx = w.astype(np.int32)  # truncation == floor: clip handles < 0
                np.clip(idx, 0, bins - 1, out=idx)
                idx += offs32
                if G > 1:
                    idx += (lab * np.int32(fp * bins))[:, None]
                hist += np.bincount(idx.ravel(), minlength=G * fp * bins)
        mean = s / np.maximum(cnt, 1.0)[:, None]
        m2 = np.maximum(ss - cnt[:, None] * mean**2, 0.0)
        return _result(
            plan, fp, bins, glo, ghi, nsel=nsel, n=n, cnt=cnt, mean=mean, m2=m2,
            mn=mn, mx=mx, hist=None if bins == 0 else hist.reshape(G, fp, bins),
        )

    return run


def _build_jax(plan, f, bins):
    import jax
    import jax.numpy as jnp

    from repro.kernels.plan.kernel import _JNP_OPS

    cols = plan.resolve_columns(f)
    project = cols != tuple(range(f))
    cols_arr = np.asarray(cols, np.int32)
    fp = len(cols)
    G = plan.groups
    gcol = None if plan.group_by is None else plan.group_by % f

    @jax.jit
    def fused(x, lo, invw):
        x = x.astype(jnp.float32)
        m = jnp.ones((x.shape[0],), bool)
        for p in plan.predicates:
            m = jnp.logical_and(m, _JNP_OPS[p.op](x[:, p.column], jnp.float32(p.value)))
        nsel = m.astype(jnp.float32).sum()
        xp = x[:, cols_arr] if project else x
        lab = None if gcol is None else x[:, gcol].astype(jnp.int32)
        outs = []
        for g in range(G):
            mg = m if lab is None else jnp.logical_and(m, lab == g)
            w = mg.astype(jnp.float32)
            cnt = w.sum()
            safe = jnp.maximum(cnt, 1.0)
            mean = (w @ xp) / safe
            m2 = w @ jnp.square(xp - mean)
            mn = jnp.where(mg[:, None], xp, jnp.inf).min(axis=0)
            mx = jnp.where(mg[:, None], xp, -jnp.inf).max(axis=0)
            if bins:
                idx = jnp.clip(
                    jnp.floor((xp - lo) * invw).astype(jnp.int32), 0, bins - 1
                )
                flat = idx + jnp.arange(fp, dtype=jnp.int32) * bins
                hist = (
                    jnp.zeros((fp * bins,), jnp.float32)
                    .at[flat.ravel()]
                    .add(jnp.repeat(w, fp))
                    .reshape(fp, bins)
                )
            else:
                hist = jnp.zeros((fp, 0), jnp.float32)
            outs.append((cnt, mean, m2, mn, mx, hist))
        cnts, means, m2s, mns, mxs, hists = (jnp.stack(v) for v in zip(*outs))
        return nsel, cnts, means, m2s, mns, mxs, hists

    def run(x, glo, ghi):
        import jax.numpy as jnp

        lo = np.zeros(fp) if glo is None else glo
        invw = np.zeros(fp) if bins == 0 else _inv_width(glo, ghi, bins)
        nsel, cnt, mean, m2, mn, mx, hist = to_host(fused(
            to_device(x, "plan"), jnp.asarray(lo, jnp.float32), jnp.asarray(invw, jnp.float32)
        ), "plan")
        return _result(
            plan, fp, bins, glo, ghi, nsel=float(nsel), n=x.shape[0],
            cnt=np.asarray(cnt, np.float64), mean=np.asarray(mean, np.float64),
            m2=np.asarray(m2, np.float64), mn=np.asarray(mn, np.float64),
            mx=np.asarray(mx, np.float64),
            hist=None if bins == 0 else np.rint(hist).astype(np.int64),
        )

    return run


def _build_pallas(plan, f, bins, tile_rows, interpret):
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.plan.kernel import plan_sketch_pallas

    cols = plan.resolve_columns(f)
    fp = len(cols)
    G = plan.groups
    # one jitted callable per cache entry: an eager pallas_call would
    # re-trace the kernel, and recompile it, on every block
    fused = jax.jit(
        functools.partial(
            plan_sketch_pallas, plan=plan, bins=bins, tile_rows=tile_rows,
            interpret=interpret,
        )
    )

    def run(x, glo, ghi):
        stats, hist, nsel = to_host(fused(
            to_device(x, "plan"), jnp.asarray(glo), jnp.asarray(_inv_width(glo, ghi, bins))
        ), "plan")
        stats = np.asarray(stats, np.float64).reshape(G, 5, fp)
        hist = np.rint(np.asarray(hist, np.float64)).astype(np.int64)
        return _result(
            plan, fp, bins, glo, ghi, nsel=float(nsel[0, 0]),
            n=x.shape[0], cnt=stats[:, 0, 0], mean=stats[:, 1], m2=stats[:, 2],
            mn=stats[:, 3], mx=stats[:, 4], hist=hist.reshape(G, fp, bins),
        )

    return run


# -- typed plans: int32 codes, exact predicates, expression sums per group --

def _to_device_flat(x: np.ndarray):
    """A typed block's copy to the device, flat: a TPU lays out a narrow
    ``[n, 16]`` int32 block column-major in tiles, which the host would build
    by a transposition of every copy; a flat array copies as it lies, and the
    device reshapes it (the jitted executors take ``flat.reshape(-1, F)``)."""
    return to_device(np.ascontiguousarray(x).reshape(-1), "plan")


def _build_typed_ref(plan):
    return lambda x: plan_totals_ref(x, plan)


def _build_typed_np(plan, tile_rows):
    """Cache-blocked numpy typed fold: per row tile, mask the codes, take
    the passing rows, decode the measures (float32) and add their per-group
    sums into float64 accumulators."""
    G = plan.groups

    def run(x):
        counts = np.zeros(G)
        sums = np.zeros((G, len(plan.measures)))
        nsel = 0
        for start in range(0, x.shape[0], tile_rows):
            t = x[start : start + tile_rows]
            sel = np.take(t, np.flatnonzero(plan.mask(t)), axis=0)
            if sel.shape[0] == 0:
                continue
            nsel += sel.shape[0]
            column = lambda c: sel[:, c]  # noqa: E731
            keys = np.broadcast_to(group_keys(column, plan.group_by), (sel.shape[0],))
            c, s = totals_of(keys, decode_measures(column, plan.measures), G)
            counts += c
            sums += s
        return PlanTotals(int(x.shape[0]), nsel, counts, sums)

    return run


def _typed_jax_fold(plan):
    """The jitted typed fold ``fused(flat, f)`` of a flat int32 block of
    ``f`` columns: the selected-row count, per-group counts and sums."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.kernels.plan.kernel import _JNP_OPS

    G = plan.groups

    @functools.partial(jax.jit, static_argnums=1)
    def fused(flat, f):
        x = flat.reshape(-1, f)
        column = lambda c: x[:, c]  # noqa: E731
        m = jnp.ones((x.shape[0],), bool)
        for p in plan.predicates:
            m = jnp.logical_and(m, _JNP_OPS[p.op](column(p.column), jnp.int32(p.code)))
        onehot = m[:, None]
        if plan.group_by:
            key = group_keys(column, plan.group_by)
            onehot = jnp.logical_and(onehot, key[:, None] == jnp.arange(G, dtype=jnp.int32))
        counts = onehot.sum(axis=0, dtype=jnp.int32)
        values = decode_measures(column, plan.measures, jnp)
        sums = (jnp.stack([jnp.where(onehot, v[:, None], 0.0).sum(axis=0) for v in values], 1)
                if values else jnp.zeros((G, 0), jnp.float32))
        return m.sum(dtype=jnp.int32), counts, sums

    return fused


def _build_typed_jax(plan):
    G, M = plan.groups, len(plan.measures)
    fused = _typed_jax_fold(plan)

    def run(x):
        nsel, counts, sums = to_host(fused(_to_device_flat(x), x.shape[1]), "plan")
        return PlanTotals(int(x.shape[0]), int(nsel), np.asarray(counts, np.float64),
                          np.asarray(sums, np.float64).reshape(G, M))

    return run


def _build_typed_pallas(plan, tile_rows, interpret):
    import functools

    import jax

    from repro.kernels.plan.kernel import plan_totals_pallas

    G, M = plan.groups, len(plan.measures)

    @functools.partial(jax.jit, static_argnums=1)
    def fused(flat, f):
        return plan_totals_pallas(flat.reshape(-1, f), plan=plan, tile_rows=tile_rows,
                                  interpret=interpret)

    def run(x):
        (out,) = to_host((fused(_to_device_flat(x), x.shape[1]),), "plan")
        t = np.asarray(out[:, 0], np.float64)
        per_group = t[1:].reshape(G, 1 + M)
        return PlanTotals(int(x.shape[0]), int(t[0]), per_group[:, 0].copy(),
                          per_group[:, 1:].copy())

    return run


def _tile(impl: str, tile_rows: int | None, typed: bool = False) -> int | None:
    """The tile a tiled impl runs with when none is pinned."""
    if impl in ("np", "pallas") and tile_rows is None:
        if impl == "np":
            return DEFAULT_NP_TILE
        return TYPED_PALLAS_TILE if typed else PALLAS_TILES[0]
    return tile_rows


def compile_plan(
    plan: QueryPlan,
    *,
    num_features: int,
    bins: int = 0,
    impl: str = "np",
    tile_rows: int | None = None,
) -> Callable:
    """The compiled executor ``run(x32, glo, ghi) -> PlanResult`` for
    ``plan`` at this shape, memoized on ``(plan.key(), features, bins,
    impl, tile, interpret mode)`` -- the plan-keyed compile cache."""
    global _HITS, _MISSES
    if impl not in IMPLS or impl == "auto":
        raise ValueError(f"compile_plan impl must be concrete, got {impl!r}")
    typed = isinstance(plan, TypedPlan)
    tile_rows = _tile(impl, tile_rows, typed)
    interpret = impl == "pallas" and interpret_mode()
    key = (plan.key(), int(num_features), int(bins), impl, tile_rows, interpret)
    telemetry = obs.enabled()
    with _CACHE_LOCK:
        fn = _CACHE.get(key)
        if fn is not None:
            _HITS += 1
            if telemetry:
                obs.get_registry().counter(
                    "rsp_plan_compile_total", "plan-cache lookups", outcome="hit"
                ).inc()
            return fn
    t0 = time.perf_counter()
    if typed and impl == "ref":
        fn = _build_typed_ref(plan)
    elif typed and impl == "np":
        fn = _build_typed_np(plan, tile_rows)
    elif typed and impl == "jax":
        fn = _build_typed_jax(plan)
    elif typed:
        fn = _build_typed_pallas(plan, tile_rows, interpret)
    elif impl == "ref":
        fn = _build_ref(plan, num_features, bins)
    elif impl == "np":
        fn = _build_np(plan, num_features, bins, tile_rows)
    elif impl == "jax":
        fn = _build_jax(plan, num_features, bins)
    else:
        fn = _build_pallas(plan, num_features, bins, tile_rows, interpret)
    if telemetry:
        reg = obs.get_registry()
        reg.counter("rsp_plan_compile_total", "plan-cache lookups", outcome="miss").inc()
        reg.histogram(
            "rsp_plan_compile_seconds", "executor build time on a cache miss",
            impl=impl,
        ).observe(time.perf_counter() - t0)
    with _CACHE_LOCK:
        fn = _CACHE.setdefault(key, fn)
        _MISSES += 1
    return fn


# ---------------------------------------------------------------------------
# Autotuned dispatch
# ---------------------------------------------------------------------------

def _default_candidate() -> Candidate:
    import jax

    if jax.default_backend() == "cpu":
        return Candidate("np", DEFAULT_NP_TILE)
    return Candidate("jax")


def _auto_config(plan, x, glo, ghi, *, bins) -> Candidate:
    import jax

    n, f = x.shape
    dev = jax.default_backend()
    cands = [Candidate("np", t) for t in NP_TILES]
    cands.append(Candidate("ref"))
    if dev != "cpu":
        cands.append(Candidate("jax"))
    if bins >= 1:
        # off-TPU these run the Pallas interpreter; flagged so the tuner
        # never crowns a config from interpret-mode timings
        interpreted = interpret_mode()
        cands += [
            Candidate("pallas", t, interpreted=interpreted) for t in PALLAS_TILES
        ]
    key = autotune.shape_key(n, f) + f"|g{plan.groups}p{len(plan.predicates)}c{len(plan.resolve_columns(f))}b{bins}"

    def measure(c: Candidate) -> float:
        fn = compile_plan(
            plan, num_features=f, bins=bins, impl=c.impl, tile_rows=c.tile_rows
        )
        fn(x, glo, ghi)  # warm (jit compile / first-touch) outside the timer
        t0 = time.perf_counter()
        fn(x, glo, ghi)
        return time.perf_counter() - t0

    return autotune.choose(
        "plan_sketch", key, cands, measure, default=_default_candidate()
    )


def plan_sketch(
    block,
    plan: QueryPlan,
    *,
    bins: int = 0,
    lo=0.0,
    hi=1.0,
    impl: str = "auto",
    tile_rows: int | None = None,
) -> PlanResult:
    """Execute ``plan`` over one block (any ``[n, ...]`` shape; features
    flatten) in a single fused pass.

    ``bins=0`` skips histograms (``impl="pallas"`` then falls back to the
    jit path, as its kernel always histograms).  ``lo`` / ``hi`` are
    scalars or arrays over the *projected* features.  ``impl="auto"``
    routes through the measured autotuner; an explicit ``tile_rows`` pins
    the tile for the tiled impls.
    """
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r} (one of {IMPLS})")
    x = np.asarray(block, dtype=np.float32).reshape(np.shape(block)[0], -1)
    n, f = x.shape
    fp = len(plan.resolve_columns(f))
    glo = ghi = None
    if bins > 0:
        glo, ghi = _grid(lo, hi, fp)
    if impl == "auto":
        cfg = _auto_config(plan, x, glo, ghi, bins=bins)
        impl = cfg.impl
        if tile_rows is None:
            tile_rows = cfg.tile_rows
    if impl == "pallas" and bins == 0:
        impl = "jax"
    tile_rows = _tile(impl, tile_rows)
    fn = compile_plan(plan, num_features=f, bins=bins, impl=impl, tile_rows=tile_rows)
    count_kernel_run("plan", impl, tile_rows if impl in ("np", "pallas") else None)
    return fn(x, glo, ghi)


def plan_totals(block, plan: TypedPlan, *, impl: str = "auto",
                tile_rows: int | None = None) -> PlanTotals:
    """Execute a :class:`TypedPlan` over one int32 block ``[n, F]`` in one
    pass: per group, the count of rows passing the predicates and each
    measure's float32 sum over them.  ``impl="auto"`` runs ``jax`` on an
    accelerator and ``np`` on a CPU, untuned: on a TPU v5e the jax fold
    takes less device time than the pallas one, and a one-call timing
    picked between them differently from run to run.  With telemetry on,
    ``rsp_plan_rows_total{outcome=scanned|selected}`` counts the rows, so a
    run shows each plan's selectivity."""
    if impl not in IMPLS:
        raise ValueError(f"unknown impl {impl!r} (one of {IMPLS})")
    x = np.asarray(block)
    if x.dtype != np.int32:
        raise ValueError(f"a typed fold reads int32 codes, got {x.dtype}")
    x = x.reshape(x.shape[0], -1)
    if impl == "auto":
        impl = _default_candidate().impl
    tile_rows = _tile(impl, tile_rows, typed=True)
    fn = compile_plan(plan, num_features=x.shape[1], impl=impl, tile_rows=tile_rows)
    count_kernel_run("plan", impl, tile_rows if impl in ("np", "pallas") else None)
    res = fn(x)
    if obs.enabled():
        reg = obs.get_registry()
        for outcome, rows in (("scanned", res.rows_total), ("selected", res.rows_selected)):
            reg.counter("rsp_plan_rows_total", "rows a typed fold read and passed",
                        outcome=outcome).inc(rows)
    return res
