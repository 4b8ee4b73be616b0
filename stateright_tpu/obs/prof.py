"""Continuous wave profiler: cost-model capture + roofline attribution.

The obs stack through round 19 can say *how fast* a run went (wave
events, latency histograms, SLOs) but not *why*: no compiled program
records its FLOP/byte cost. This module closes the gap in three parts:

1. **Static cost capture.** Every program built through the engines'
   ``_cached_program`` funnel records its XLA cost model at compile
   time — ``compiled.cost_analysis()`` (flops, bytes accessed) and
   ``compiled.memory_analysis()`` (argument/output/temp bytes, summed
   to a peak-memory estimate) — keyed by the canonical program key.
   Records live in a **process-wide** table on purpose: the shared jit
   cache (``jit_cache.WaveProgramCache``) hands the same compiled
   program to every engine instance in the process, so a record
   captured at first build must be findable from an instance that only
   ever saw a cache hit. Hits pay a dict lookup; rebuilds pay nothing.
2. **Sampled stage timing.** Every Nth dispatch (``STpu_PROF_SAMPLE``,
   default 32 — plus the first dispatch of every program key, so every
   compiled program gets at least one measurement) is timed to a rest
   point with ``block_until_ready``. The measured seconds against the
   static record yield the roofline gauges — achieved flops/s, bytes/s,
   arithmetic intensity — emitted as a ``profile_snapshot`` event
   (schema v13) through the producer's tracer (and relay, and flight
   ring), plus the nullable wave fields ``cost_flops`` / ``cost_bytes``
   / ``cost_ratio`` stamped centrally like every versioned wave key.
3. **Compile-regression detection.** ``cost_ratio`` is the sampled wave
   seconds normalized by the program's OWN first sampled baseline —
   always finite, 1.0 at the baseline, drifting up when the same
   program gets slower. The slow-wave detector (``obs/anomaly.py``)
   reads it off the wave entry and attributes a ``cost_model`` cause
   when a key's ratio drifts from its ratio history while the program
   runs.

Honesty notes, load-bearing for reading the numbers:

- **Sampling perturbs the pipeline.** The rest-point
  ``block_until_ready`` serializes the sampled dispatch against its
  pipeline (classic dispatch-ahead, fused multi-dispatch inflight), so
  1/N waves pay a join the unprofiled run overlaps. MEASUREMENTS.md
  carries the armed-vs-disarmed A/B; at the default cadence the delta
  sits inside rep spread on the 1-core CI box.
- **CPU cost models are approximate.** The CPU backend's
  ``cost_analysis()`` reports optimized-HLO flop/byte counts (returned
  as a single-element list of dicts — handled here), with no
  ``optimal_seconds``; a fallback program that never AOT-compiled
  (``jax.jit`` lazy path) exposes no cost analysis at all and records
  null flops/bytes. ``cost_ratio`` is defined against the program's
  own measured history precisely so it stays meaningful on every
  backend, with or without a cost model.

Disarmed (``STpu_PROF`` unset): ``prof_from_env`` returns the shared
:data:`NULL_PROF` and every producer hot loop pays one attribute check
(``if self._prof.enabled:``) — the poisoned-null test pins this like
rounds 8/18.

Dependency-free beyond ``obs.schema`` (no jax, no numpy): the capture
helpers duck-type the compiled executable, so the tools and tests
import this without a backend.
"""

from __future__ import annotations

import math
import os
import threading
from typing import Dict, List, Optional

__all__ = [
    "PROF_ENV", "PROF_SAMPLE_ENV", "WaveProfiler", "NullWaveProfiler",
    "NULL_PROF", "prof_from_env", "cost_record", "roofline",
    "program_records", "clear_program_records", "prometheus_prof_lines",
]

#: Environment knob: ``STpu_PROF=1`` arms the continuous profiler.
#: Unset/``0`` means the shared null profiler — one attribute check
#: per dispatch.
PROF_ENV = "STpu_PROF"

#: Environment knob: sample every Nth dispatch (default 32). ``1``
#: times every dispatch (offline profiling / tests); the first
#: dispatch of each program key is always sampled regardless.
PROF_SAMPLE_ENV = "STpu_PROF_SAMPLE"

_SAMPLE_DEFAULT = 32

#: Process-wide static cost records: canonical program key ->
#: ``{"flops", "bytes", "peak_bytes", "kernel_path"}``. See the module
#: docstring for why this is process-global rather than per-profiler.
_COST_LOCK = threading.Lock()
_COST_RECORDS: Dict[str, dict] = {}


def cost_record(program) -> Optional[dict]:
    """Extracts the static cost model of one AOT-compiled executable:
    ``{"flops", "bytes", "peak_bytes", "kernel_path": None}``. Returns
    ``None`` when the object exposes no ``cost_analysis`` (the lazy
    ``jax.jit`` fallback, a host callable) — callers record a null-cost
    entry so the key is still attributed. Never raises."""
    try:
        ca = program.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        # The CPU client returns a single-element list of dicts.
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None
    try:
        flops = float(ca.get("flops", 0.0) or 0.0)
        byts = float(ca.get("bytes accessed", 0.0) or 0.0)
    except (TypeError, ValueError):
        return None
    rec = {"flops": flops, "bytes": byts, "peak_bytes": None,
           "kernel_path": None}
    try:
        ma = program.memory_analysis()
        rec["peak_bytes"] = int(
            getattr(ma, "argument_size_in_bytes", 0)
            + getattr(ma, "output_size_in_bytes", 0)
            + getattr(ma, "temp_size_in_bytes", 0))
    except Exception:
        pass  # the cost half alone is still worth recording
    return rec


def roofline(rec: Optional[dict], measured_s: float) -> dict:
    """The roofline gauges for one measured execution of a program with
    static record ``rec``: achieved flops/s and bytes/s, and arithmetic
    intensity (flops per byte accessed — the roofline x-axis). All
    ``None`` when the program has no cost record."""
    out = {"flops": None, "bytes": None, "peak_bytes": None,
           "flops_per_s": None, "bytes_per_s": None, "intensity": None}
    if not rec:
        return out
    flops, byts = rec.get("flops"), rec.get("bytes")
    out["flops"], out["bytes"] = flops, byts
    out["peak_bytes"] = rec.get("peak_bytes")
    if isinstance(flops, (int, float)) and measured_s > 0:
        out["flops_per_s"] = round(flops / measured_s, 3)
    if isinstance(byts, (int, float)) and measured_s > 0:
        out["bytes_per_s"] = round(byts / measured_s, 3)
    if isinstance(flops, (int, float)) and isinstance(byts, (int, float)) \
            and byts > 0:
        out["intensity"] = round(flops / byts, 6)
    return out


def program_records(prefix: Optional[str] = None) -> Dict[str, dict]:
    """A copy of the process-wide cost-record table, optionally
    filtered to keys starting with ``prefix`` (program keys lead with
    the producer id, so a producer's own programs filter cleanly)."""
    with _COST_LOCK:
        return {k: dict(v) for k in sorted(_COST_RECORDS)
                if prefix is None or k.startswith(prefix)
                for v in (_COST_RECORDS[k],)}


def clear_program_records() -> None:
    """Drops every static record (tests only — the table is otherwise
    append-only for the life of the process, like the jit cache)."""
    with _COST_LOCK:
        _COST_RECORDS.clear()


class NullWaveProfiler:
    """The disarmed profiler: every method a no-op, ``enabled`` False.
    Hot paths must check ``enabled`` BEFORE calling anything — the
    disarmed-cost test poisons these methods, so a stray call (= a
    stray per-dispatch cost with the subsystem off) fails the suite."""

    __slots__ = ()
    enabled = False
    armed = False

    def capture(self, key, program) -> None:
        pass

    def should_sample(self, key=None) -> bool:
        return False

    def wave(self, entry, key=None, measured_s=None, tracer=None,
             flight=None) -> None:
        pass

    def stats(self) -> dict:
        return {}

    def close(self, tracer=None) -> None:
        pass


#: The shared disarmed profiler (``prof_from_env`` returns this very
#: object when ``STpu_PROF`` is unset — identity-testable).
NULL_PROF = NullWaveProfiler()


class WaveProfiler:
    """Per-producer continuous profiler: capture at compile, sample at
    dispatch, stamp at the wave event. One instance per producer
    (engine, elastic worker, offline profiling run) so the sampling
    cadence and the snapshot ordinal are per producer; the static cost
    table is shared process-wide (module docstring)."""

    enabled = True
    armed = True

    def __init__(self, producer: str, sample_every: int = _SAMPLE_DEFAULT):
        self.producer = str(producer)
        self.sample_every = max(1, int(sample_every))
        self._lock = threading.Lock()
        self._dispatches = 0
        self._sampled = 0
        self._snap = 0
        self._captured = 0
        #: per-key first sampled seconds — the cost_ratio denominator.
        self._baseline: Dict[str, float] = {}
        #: per-key latest snapshot payload (the live-metrics surface).
        self._last: Dict[str, dict] = {}
        #: keys that have had at least one sampled dispatch.
        self._seen: set = set()

    # -- Compile-time capture ----------------------------------------------

    def capture(self, key: str, program) -> None:
        """Records ``program``'s static cost model under ``key`` if no
        record exists yet (cold path: runs at most once per program per
        process — compile dwarfs it; shared-cache hits find the
        first builder's record)."""
        with _COST_LOCK:
            if key in _COST_RECORDS:
                return
        rec = cost_record(program)
        if rec is None:
            # No AOT cost analysis (lazy-jit fallback): a null-cost
            # record still attributes the key and stops re-probing.
            rec = {"flops": None, "bytes": None, "peak_bytes": None,
                   "kernel_path": None}
        with _COST_LOCK:
            _COST_RECORDS.setdefault(key, rec)
        with self._lock:
            self._captured += 1

    # -- Dispatch-time sampling --------------------------------------------

    def should_sample(self, key: Optional[str] = None) -> bool:
        """One call per dispatch (armed paths only). True every
        ``sample_every``-th dispatch, and ALWAYS on the first dispatch
        of a new program key — so every compiled program carries at
        least one measured ``cost_ratio``. Deterministic: same dispatch
        sequence, same sampled set."""
        with self._lock:
            n = self._dispatches
            self._dispatches += 1
            first = key is not None and key not in self._seen
            if key is not None:
                self._seen.add(key)
        return first or n % self.sample_every == 0

    def wave(self, entry: dict, key: Optional[str] = None,
             measured_s: Optional[float] = None, tracer=None,
             flight=None) -> None:
        """Stamps the v13 cost fields onto one dispatch-log entry (the
        same dict the tracer, the flight ring, and the anomaly detector
        see) and, when the dispatch was sampled (``measured_s`` set),
        emits a ``profile_snapshot`` event with the roofline gauges."""
        rec = None
        if key is not None:
            with _COST_LOCK:
                rec = _COST_RECORDS.get(key)
            if rec is not None and rec.get("kernel_path") is None:
                kp = entry.get("kernel_path")
                if kp is not None:
                    with _COST_LOCK:
                        rec["kernel_path"] = kp
        entry["cost_flops"] = rec.get("flops") if rec else None
        entry["cost_bytes"] = rec.get("bytes") if rec else None
        ratio = None
        if measured_s is not None and key is not None:
            measured_s = max(float(measured_s), 1e-9)
            if math.isfinite(measured_s):
                with self._lock:
                    base = self._baseline.get(key)
                    if base is None:
                        base = self._baseline[key] = measured_s
                    self._sampled += 1
                    self._snap += 1
                    snap = self._snap
                ratio = round(measured_s / base, 6)
                evt = dict(roofline(rec, measured_s), key=key,
                           kernel_path=entry.get("kernel_path"),
                           expand_impl=entry.get("expand_impl"),
                           snap=snap, measured_s=round(measured_s, 6),
                           cost_ratio=ratio)
                with self._lock:
                    self._last[key] = dict(evt)
                if tracer is not None and tracer.enabled:
                    tracer.event("profile_snapshot", **evt)
                if flight is not None and flight.armed:
                    flight.record_event("profile_snapshot", **evt)
        entry["cost_ratio"] = ratio

    # -- Surfaces -----------------------------------------------------------

    def stats(self) -> dict:
        """The aggregated view ``scheduler_stats`` / bench /
        ``GET /.metrics`` surface as ``prof``."""
        with self._lock:
            last = {k: dict(self._last[k]) for k in sorted(self._last)}
            return {"dispatches": self._dispatches,
                    "sampled": self._sampled,
                    "sample_every": self.sample_every,
                    "captured": self._captured,
                    "programs": last}

    def close(self, tracer=None) -> None:
        """Teardown hook for API symmetry with the sibling facades.
        Snapshots are emitted per sample (nothing cumulative is held
        back), so there is nothing to flush."""


def prometheus_prof_lines(stats: dict, producer: str,
                          prefix: str = "stpu_") -> List[str]:
    """Prometheus exposition lines for one profiler's ``stats()``
    payload — the ``stpu_prof_*`` families on ``GET /.metrics``."""
    if not stats:
        return []
    esc = str(producer).replace('"', "'")
    lines = [
        f'{prefix}prof_dispatches_total{{engine="{esc}"}} '
        f'{int(stats.get("dispatches") or 0)}',
        f'{prefix}prof_sampled_total{{engine="{esc}"}} '
        f'{int(stats.get("sampled") or 0)}',
        f'{prefix}prof_programs{{engine="{esc}"}} '
        f'{len(stats.get("programs") or {})}',
    ]
    for key, snap in sorted((stats.get("programs") or {}).items()):
        kesc = str(key).replace('"', "'")
        base = f'engine="{esc}",key="{kesc}"'
        for field, family in (("flops", "prof_flops"),
                              ("bytes", "prof_bytes"),
                              ("flops_per_s", "prof_flops_per_s"),
                              ("bytes_per_s", "prof_bytes_per_s"),
                              ("intensity", "prof_intensity"),
                              ("cost_ratio", "prof_cost_ratio"),
                              ("measured_s", "prof_measured_seconds")):
            val = snap.get(field)
            if isinstance(val, (int, float)) and not isinstance(val, bool):
                lines.append(f"{prefix}{family}{{{base}}} {val}")
    return lines


def prof_from_env(producer: str):
    """The profiler factory every producer uses: the shared
    :data:`NULL_PROF` when ``STpu_PROF`` is unset/``0`` (no
    allocation, one attribute check per dispatch); an armed
    :class:`WaveProfiler` otherwise, with the ``STpu_PROF_SAMPLE``
    cadence."""
    if os.environ.get(PROF_ENV, "") in ("", "0"):
        return NULL_PROF
    try:
        sample = int(os.environ.get(PROF_SAMPLE_ENV, "")
                     or _SAMPLE_DEFAULT)
    except ValueError:
        sample = _SAMPLE_DEFAULT
    return WaveProfiler(producer, sample_every=sample)
