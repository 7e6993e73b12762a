"""``repro.kernels.autotune`` -- measure-based tile autotuner shared by the
RSP kernels.

Every tiled kernel in the repo used to hardcode its tile size
(``tile_rows=128`` and friends).  The right tile depends on the machine:
cache sizes on CPU hosts, VMEM pressure and grid occupancy on TPUs.  This
module replaces the constants with a tiny measured search:

* On the first ``impl="auto"`` call for a given ``(kernel, shape bucket,
  dtype, device)`` key, each candidate config is timed on the *actual*
  workload (best-of-``repeats``, so a noisy neighbour cannot crown a loser)
  and the fastest wins.
* The winner is persisted to ``results/bench/autotune.json`` (atomic
  rename), so later processes skip the measurement entirely.  Shapes are
  bucketed to the next power of two in rows -- one measurement covers the
  whole bucket.
* **Interpret-mode Pallas timings never decide.**  Off-TPU the Pallas
  kernels run under ``interpret=True``, which measures the interpreter,
  not the kernel; candidates flagged ``interpreted`` are excluded from
  selection (they would otherwise "lose" to numpy by 100x for reasons that
  vanish on real hardware).  If every candidate is excluded the pinned
  default wins and the record says so.
* **A compiled kernel that fails is an error, not a slow candidate.**  On
  a TPU an exception from a Pallas candidate propagates: a kernel the
  chip's compiler refuses must not turn silently into a numpy run.  Other
  candidates that raise are disqualified and recorded as ``(error)``.
* ``REPRO_AUTOTUNE=off`` (or ``0`` / ``false``) disables measurement
  everywhere: ``choose`` returns the pinned default immediately and
  touches no files.  CI and the tier-1 tests run in this mode, so test
  outcomes never depend on machine-local timings.

Consumers: ``repro.kernels.plan`` (fused query-plan kernels),
``repro.kernels.block_sketch`` (``impl="auto"`` + Pallas tile), and
``repro.kernels.rsp_shuffle`` (``tile_rows=None``).
"""

from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from typing import Callable, Sequence

_ENV = "REPRO_AUTOTUNE"
_ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
_OFF = ("off", "0", "false", "no")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One tunable configuration: an implementation name plus its tile size
    (``None`` when the impl is untiled).  ``interpreted=True`` marks a
    config whose measurement would time an interpreter (Pallas off-TPU);
    such candidates are never selected from measurements."""

    impl: str
    tile_rows: int | None = None
    interpreted: bool = False

    @property
    def label(self) -> str:
        return self.impl if self.tile_rows is None else f"{self.impl}:{self.tile_rows}"


def enabled() -> bool:
    """Whether measurement is allowed (``REPRO_AUTOTUNE`` not off)."""
    return os.environ.get(_ENV, "on").strip().lower() not in _OFF


def cache_path() -> str:
    """Where winners persist: ``$REPRO_AUTOTUNE_CACHE`` or the repo's
    ``results/bench/autotune.json``."""
    env = os.environ.get(_ENV_CACHE)
    if env:
        return env
    root = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))
    return os.path.join(root, "results", "bench", "autotune.json")


def shape_key(rows: int, features: int, dtype: str = "float32") -> str:
    """Bucket ``rows`` to the next power of two so one measurement covers
    nearby shapes; features and dtype are exact."""
    b = 1 << max(0, int(rows) - 1).bit_length()
    return f"r{b}xf{int(features)}:{dtype}"


def _device() -> str:
    import jax

    return jax.default_backend()


class Autotuner:
    """In-memory + on-disk cache of measured winners (see module docs)."""

    def __init__(self, path: str | None = None):
        self._path = path
        self._lock = threading.RLock()
        self._mem: dict[str, dict] = {}
        self._loaded = False
        self.measurements = 0  # total tuning runs this process (test hook)

    def _file(self) -> str:
        return self._path or cache_path()

    def _load(self) -> None:
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self._file()) as f:
                disk = json.load(f)
            if isinstance(disk, dict):
                for k, v in disk.items():
                    self._mem.setdefault(k, v)
        except (OSError, ValueError):
            pass

    def _persist(self) -> None:
        path = self._file()
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            disk: dict = {}
            try:
                with open(path) as f:
                    old = json.load(f)
                if isinstance(old, dict):
                    disk.update(old)
            except (OSError, ValueError):
                pass
            disk.update(self._mem)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(disk, f, indent=2, sort_keys=True)
                f.write("\n")
            os.replace(tmp, path)
        except OSError:
            pass  # tuning still works this process; it just won't persist

    def clear(self) -> None:
        """Forget every winner (memory and disk)."""
        with self._lock:
            self._mem.clear()
            self._loaded = False
            try:
                os.remove(self._file())
            except OSError:
                pass

    def records(self) -> dict[str, dict]:
        """Every winner record (``kernel|key|device`` -> record), measured
        in this process or loaded from disk."""
        with self._lock:
            self._load()
            return dict(self._mem)

    def lookup(self, kernel: str, key: str) -> Candidate | None:
        """The cached winner for ``(kernel, key, device)``, or None."""
        with self._lock:
            self._load()
            rec = self._mem.get(f"{kernel}|{key}|{_device()}")
        if not rec:
            return None
        return Candidate(impl=rec["impl"], tile_rows=rec.get("tile_rows"))

    def choose(
        self,
        kernel: str,
        key: str,
        candidates: Sequence[Candidate],
        measure: Callable[[Candidate], float],
        *,
        default: Candidate,
        repeats: int = 3,
    ) -> Candidate:
        """The winning :class:`Candidate` for ``(kernel, key, device)``.

        With tuning disabled returns ``default`` untouched.  Otherwise the
        cached winner is returned if present; else every non-``interpreted``
        candidate is timed ``repeats`` times via ``measure`` (which returns
        seconds for one run; exceptions disqualify the candidate, except a
        compiled Pallas candidate's on a TPU, which propagates), the
        best-of-N fastest wins, and the winner persists to
        :func:`cache_path`.  If no candidate is measurable the ``default``
        wins and the record notes the fallback.
        """
        if not enabled():
            return default
        cached = self.lookup(kernel, key)
        if cached is not None:
            return cached
        with self._lock:
            cached = self.lookup(kernel, key)
            if cached is not None:
                return cached
            t_tune = time.perf_counter()
            measured: dict[str, float] = {}
            excluded: list[str] = []
            best: Candidate | None = None
            best_t = float("inf")
            for c in candidates:
                if c.interpreted:
                    excluded.append(f"{c.label} (interpret)")
                    continue
                try:
                    t = min(measure(c) for _ in range(max(1, repeats)))
                except Exception:
                    if c.impl == "pallas" and _device() == "tpu":
                        raise
                    excluded.append(f"{c.label} (error)")
                    continue
                measured[c.label] = t * 1e6
                if t < best_t:
                    best, best_t = c, t
            self.measurements += 1
            winner = best if best is not None else default
            from repro import obs  # deferred: keep this module import-light

            if obs.enabled():
                reg = obs.get_registry()
                reg.counter(
                    "rsp_autotune_runs_total", "tuning measurement runs",
                    kernel=kernel,
                ).inc()
                reg.histogram(
                    "rsp_autotune_measure_seconds",
                    "wall time spent timing candidates for one tuning run",
                    kernel=kernel,
                ).observe(time.perf_counter() - t_tune)
            rec = {
                "impl": winner.impl,
                "tile_rows": winner.tile_rows,
                "us": None if best is None else best_t * 1e6,
                "measured_us": measured,
                "excluded": excluded,
                "fallback": best is None,
            }
            self._mem[f"{kernel}|{key}|{_device()}"] = rec
            self._persist()
            return winner


_TUNER = Autotuner()


def get_tuner() -> Autotuner:
    return _TUNER


def choose(*args, **kwargs) -> Candidate:
    """Module-level convenience for :meth:`Autotuner.choose` on the shared
    process-wide tuner."""
    return _TUNER.choose(*args, **kwargs)


def clear() -> None:
    _TUNER.clear()
