#!/usr/bin/env python
"""Exports an ``STpu_TRACE`` JSONL capture to analysis-ready formats.

Two exporters, one pass over the stream:

- **Chrome trace-event JSON** (``-o out.json``, the default with the
  input name + ``.chrome.json``): loadable in Perfetto
  (https://ui.perfetto.dev) or ``chrome://tracing``. Each run becomes a
  process track (named ``engine run``); wave events render as complete
  ("X") slices whose duration is the gap to the previous wave of the
  same run (the host-side processing interval the dispatch landed in),
  spans render on their own thread rows by depth, and cumulative
  ``states`` / ``load_factor`` render as counter ("C") tracks so the
  throughput line and the table pressure are visible against the waves
  that caused them. Timestamps are per-run relative (monotonic clocks
  from different processes don't share a base) — EXCEPT the elastic
  family (schema v5): the coordinator gets ONE track and every elastic
  worker gets ONE track keyed by worker name (run rotations from
  migrations collapse onto the same row), all sharing one time base,
  so a kill/join drill reads as parallel worker lanes under a
  coordinator lane whose membership events (worker_lost / migrate_done
  / rebalance / straggler) are instants at the moment the lanes
  change. Same-host monotonic clocks make the shared base sound for
  the transports this runtime ships. Flight-recorder postmortem dumps
  (``obs/flight.py``) are accepted as input — the ``postmortem``
  header renders as an instant ahead of the ring's events.
- **Prometheus text dump** (``--prom out.prom``): final tallies per run
  in exposition format — states/unique/waves/overflow totals, last load
  factor, counter totals, per-span-name cumulative seconds. The same
  families the explorer's live ``GET /.metrics`` serves, so dashboards
  can consume a dead run's trace and a live checker identically.

Continuous-profiler events (schema v13): ``profile_snapshot`` renders
as Perfetto counter tracks — achieved flops/s and bytes/s plus the
``cost_ratio`` drift line, one series per compiled-program key, so a
program getting slower plots against the waves where it happened — and
the Prometheus dump carries the last snapshot per (engine, key) as the
same ``stpu_prof_*`` gauge families the live ``GET /.metrics`` serves.

Dependency-free beyond the obs schema (no jax)."""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict, List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from stateright_tpu.obs.schema import SCHEMA_VERSION  # noqa: E402


def load_events(path: str) -> List[dict]:
    events = []
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except ValueError:
                continue
            if isinstance(obj, dict):
                events.append(obj)
    return events


_ELASTIC_ENGINES = ("elastic", "elastic_worker")


def _run_key(evt: dict) -> str:
    """One track per run — except the elastic family, where the track
    is the WORKER (or the coordinator): migration rotates run ids, and
    the useful timeline is lanes per participant, not per attempt."""
    engine = evt.get("engine", "?")
    if engine == "elastic_worker":
        return f"elastic worker {evt.get('worker', '?')}"
    if engine == "elastic":
        return "elastic coordinator"
    return f"{engine} {evt.get('run', '?')}"


def to_chrome(events: List[dict]) -> dict:
    """Chrome trace-event JSON (Perfetto-loadable)."""
    trace: List[dict] = []
    pids: Dict[str, int] = {}
    t0: Dict[str, float] = {}      # per-run time base
    prev_wave_t: Dict[str, float] = {}
    # One shared base for the whole elastic family: same-host
    # monotonic clocks, and the worker lanes must line up against the
    # coordinator's membership instants.
    elastic_t0 = min((e["t"] for e in events
                      if e.get("engine") in _ELASTIC_ENGINES
                      and isinstance(e.get("t"), (int, float))),
                     default=None)

    def pid_for(evt: dict) -> int:
        key = _run_key(evt)
        if key not in pids:
            pids[key] = len(pids) + 1
            trace.append({"ph": "M", "pid": pids[key], "tid": 0,
                          "name": "process_name",
                          "args": {"name": key}})
        return pids[key]

    def us(evt: dict, t: float) -> float:
        if evt.get("engine") in _ELASTIC_ENGINES \
                and elastic_t0 is not None:
            return max(0.0, (t - elastic_t0) * 1e6)
        run = evt.get("run", "?")
        base = t0.setdefault(run, t)
        return max(0.0, (t - base) * 1e6)

    for evt in events:
        etype = evt.get("type")
        t = evt.get("t")
        if etype is None or not isinstance(t, (int, float)):
            continue  # not a trace event: no track
        pid = pid_for(evt)
        run = evt.get("run", "?")
        if evt.get("engine") == "elastic_worker":
            # Waves from one worker interleave across rotated runs on
            # one lane: slice duration keys on the TRACK, not the run.
            run = _run_key(evt)
        if etype == "run_start":
            t0.setdefault(run, t)
            trace.append({"ph": "i", "pid": pid, "tid": 1,
                          "name": "run_start", "ts": us(evt, t),
                          "s": "p", "args": evt.get("meta", {})})
        elif etype == "wave":
            start = prev_wave_t.get(run, t0.get(run, t))
            prev_wave_t[run] = t
            args = {k: v for k, v in evt.items()
                    if k not in ("type", "run", "engine",
                                 "schema_version", "t")}
            trace.append({
                "ph": "X", "pid": pid, "tid": 1,
                "name": f"wave B={evt.get('bucket')}",
                "ts": us(evt, start),
                "dur": max(0.0, (t - start) * 1e6), "args": args})
            for counter, value in (("states", evt.get("states")),
                                   ("load_factor",
                                    evt.get("load_factor"))):
                if value is not None:
                    trace.append({"ph": "C", "pid": pid, "tid": 0,
                                  "name": counter, "ts": us(evt, t),
                                  "args": {counter: value}})
            # Tiered-store byte gauges (schema v6): one counter track
            # with a series per tier, so pressure reads as the device
            # line flattening while host/disk climb.
            tiers = {tier: evt.get(f"tier_{tier}_bytes")
                     for tier in ("device", "host", "disk")}
            if any(v is not None for v in tiers.values()):
                trace.append({
                    "ph": "C", "pid": pid, "tid": 0,
                    "name": "tier_bytes", "ts": us(evt, t),
                    "args": {k: v for k, v in tiers.items()
                             if v is not None}})
        elif etype == "span":
            dur = float(evt.get("dur", 0.0))
            trace.append({
                "ph": "X", "pid": pid,
                "tid": 2 + int(evt.get("depth", 0)),
                "name": str(evt.get("name", "span")),
                "ts": us(evt, t), "dur": dur * 1e6,
                "args": evt.get("attrs", {})})
        elif etype == "straggler":
            # Straggler attribution (schema v5): an instant on the
            # coordinator lane plus a wait-share counter track, so
            # barrier cost plots against the worker lanes causing it.
            trace.append({
                "ph": "i", "pid": pid, "tid": 1, "name": "straggler",
                "ts": us(evt, t), "s": "p",
                "args": {"round": evt.get("round"),
                         "slowest": evt.get("slowest"),
                         "wait_share": evt.get("wait_share"),
                         "workers": evt.get("workers", {})}})
            trace.append({"ph": "C", "pid": pid, "tid": 0,
                          "name": "wait_share", "ts": us(evt, t),
                          "args": {"wait_share":
                                   evt.get("wait_share", 0)}})
        elif etype in ("grow", "overflow_redispatch",
                       # Resilience markers (schema v3): process-scoped
                       # instants so a Perfetto timeline shows exactly
                       # where a run faulted, degraded, and recovered.
                       "fault", "recover", "degrade", "abort",
                       # Membership markers (schema v4): where a worker
                       # was lost, its partitions migrated, and a join
                       # rebalanced — the states/s dip between a
                       # worker_lost and its migrate_done is the
                       # migration cost a timeline makes visible.
                       "worker_lost", "worker_join", "migrate_done",
                       "rebalance", "retry",
                       # Flight-recorder dump header (schema v5): the
                       # postmortem file is valid exporter input.
                       "postmortem",
                       # Tiered-store markers (schema v6): where rows
                       # moved down a tier, paged back in, or a tier
                       # crossed its budget.
                       "spill", "page_in", "pressure",
                       # Job-service lifecycle (schema v7): a job trace
                       # renders submit -> done/abort as process-scoped
                       # instants bracketing the engine's run.
                       "job_submit", "job_done", "job_abort"):
            trace.append({
                "ph": "i", "pid": pid, "tid": 1, "name": etype,
                "ts": us(evt, t),
                "s": "p" if etype in ("fault", "recover", "degrade",
                                      "abort", "worker_lost",
                                      "worker_join", "migrate_done",
                                      "rebalance", "retry",
                                      "postmortem", "job_submit",
                                      "job_done", "job_abort") else "t",
                "args": {k: v for k, v in evt.items()
                         if k not in ("type", "run", "engine",
                                      "schema_version", "t")}})
        elif etype == "profile_snapshot":
            # Roofline counter tracks (schema v13): one series per
            # compiled-program key, so the achieved rates and the
            # drift ratio plot against the waves that produced them.
            key = str(evt.get("key", "?"))
            rates = {k: evt[k] for k in ("flops_per_s", "bytes_per_s")
                     if isinstance(evt.get(k), (int, float))}
            if rates:
                trace.append({"ph": "C", "pid": pid, "tid": 0,
                              "name": f"roofline {key}",
                              "ts": us(evt, t), "args": rates})
            ratio = evt.get("cost_ratio")
            if isinstance(ratio, (int, float)):
                trace.append({"ph": "C", "pid": pid, "tid": 0,
                              "name": f"cost_ratio {key}",
                              "ts": us(evt, t),
                              "args": {"cost_ratio": ratio}})
        elif etype in ("counter", "gauge"):
            trace.append({"ph": "C", "pid": pid, "tid": 0,
                          "name": str(evt.get("name", etype)),
                          "ts": us(evt, t),
                          "args": {"value": evt.get("value", 0)}})
        elif etype == "run_end":
            trace.append({"ph": "i", "pid": pid, "tid": 1,
                          "name": "run_end", "ts": us(evt, t),
                          "s": "p",
                          "args": {"dur": evt.get("dur"),
                                   "counters": evt.get("counters", {})}})
    return {"traceEvents": trace, "displayTimeUnit": "ms",
            "otherData": {"schema_version": SCHEMA_VERSION}}


def to_prometheus(events: List[dict]) -> str:
    """Final tallies in Prometheus exposition format, labeled per run."""
    finals: Dict[str, dict] = {}
    span_sec: Dict[tuple, float] = {}
    counter_final: Dict[tuple, float] = {}
    overflows: Dict[str, int] = {}
    grows: Dict[str, int] = {}
    # v11: the LAST hist_snapshot per (run, series) — snapshots are
    # cumulative, so the final one is the run's whole distribution.
    hist_finals: Dict[str, Dict[str, dict]] = {}
    spills: Dict[str, int] = {}
    spill_bytes: Dict[str, float] = {}
    page_ins: Dict[str, int] = {}
    # v13: the LAST profile_snapshot per (engine, program key) — the
    # baseline-relative gauges supersede earlier samples — plus the
    # per-engine sampled totals.
    prof_finals: Dict[tuple, dict] = {}
    prof_sampled: Dict[str, int] = {}
    worker_wait: Dict[str, float] = {}
    worker_compute: Dict[str, float] = {}
    max_wait_share = None
    for evt in events:
        etype = evt.get("type")
        run = evt.get("run", "?")
        engine = evt.get("engine", "?")
        if etype == "wave":
            finals[run] = dict(evt, engine=engine)
        elif etype == "straggler":
            share = evt.get("wait_share", 0)
            max_wait_share = (share if max_wait_share is None
                              else max(max_wait_share, share))
            for w, seg in (evt.get("workers") or {}).items():
                worker_wait[w] = worker_wait.get(w, 0.0) \
                    + float(seg.get("wait_s") or 0.0)
                worker_compute[w] = worker_compute.get(w, 0.0) \
                    + float(seg.get("compute_s") or 0.0)
        elif etype == "span":
            key = (engine, run, evt.get("name", "span"))
            span_sec[key] = span_sec.get(key, 0.0) + float(
                evt.get("dur", 0.0))
        elif etype == "counter":
            counter_final[(engine, run, evt.get("name", "counter"))] = \
                evt.get("value", 0)
        elif etype == "overflow_redispatch":
            overflows[run] = overflows.get(run, 0) + 1
        elif etype == "grow":
            grows[run] = grows.get(run, 0) + 1
        elif etype == "spill":
            spills[run] = spills.get(run, 0) + 1
            spill_bytes[run] = spill_bytes.get(run, 0) \
                + float(evt.get("bytes") or 0)
        elif etype == "page_in":
            page_ins[run] = page_ins.get(run, 0) + 1
        elif etype == "hist_snapshot":
            hists = evt.get("hists")
            if isinstance(hists, dict):
                hist_finals.setdefault(run, {}).update(hists)
        elif etype == "profile_snapshot":
            prof_finals[(engine, str(evt.get("key", "?")))] = evt
            prof_sampled[engine] = prof_sampled.get(engine, 0) + 1

    lines: List[str] = []

    def emit(metric: str, mtype: str, rows) -> None:
        rows = list(rows)
        if not rows:
            return
        lines.append(f"# TYPE {metric} {mtype}")
        for labels, value in rows:
            label_s = ",".join(f'{k}="{v}"' for k, v in labels.items())
            lines.append(f"{metric}{{{label_s}}} {value}")

    def final_rows(field):
        for run, evt in sorted(finals.items()):
            value = evt.get(field)
            if value is not None:
                yield {"engine": evt["engine"], "run": run}, value

    emit("stpu_states_total", "counter", final_rows("states"))
    emit("stpu_unique_states_total", "counter", final_rows("unique"))
    emit("stpu_waves_total", "counter",
         (({"engine": evt["engine"], "run": run}, evt.get("wave", 0) + 1)
          for run, evt in sorted(finals.items())))
    emit("stpu_table_load_factor", "gauge", final_rows("load_factor"))
    emit("stpu_overflow_redispatches_total", "counter",
         (({"run": run}, n) for run, n in sorted(overflows.items())))
    emit("stpu_table_grows_total", "counter",
         (({"run": run}, n) for run, n in sorted(grows.items())))
    # Tiered-store families (schema v6): final per-tier residency off
    # the last wave event, plus spill/page-in totals — the same
    # families the explorer's live /.metrics serves.
    emit("stpu_tier_bytes", "gauge",
         (({"engine": evt["engine"], "run": run, "tier": tier}, value)
          for run, evt in sorted(finals.items())
          for tier in ("device", "host", "disk")
          for value in (evt.get(f"tier_{tier}_bytes"),)
          if value is not None))
    emit("stpu_tier_spills_total", "counter",
         (({"run": run}, n) for run, n in sorted(spills.items())))
    emit("stpu_tier_spill_bytes_total", "counter",
         (({"run": run}, round(v, 1))
          for run, v in sorted(spill_bytes.items())))
    emit("stpu_tier_page_ins_total", "counter",
         (({"run": run}, n) for run, n in sorted(page_ins.items())))
    emit("stpu_span_seconds_total", "counter",
         (({"engine": e, "run": r, "name": n}, round(v, 6))
          for (e, r, n), v in sorted(span_sec.items())))
    emit("stpu_counter_total", "counter",
         (({"engine": e, "run": r, "name": n}, v)
          for (e, r, n), v in sorted(counter_final.items())))
    # Straggler attribution (schema v5): per-worker barrier-wait and
    # compute seconds plus the worst round's wait share — the same
    # families the live elastic ``GET /.metrics`` exports.
    emit("stpu_worker_wait_seconds_total", "counter",
         (({"worker": w}, round(v, 6))
          for w, v in sorted(worker_wait.items())))
    emit("stpu_worker_compute_seconds_total", "counter",
         (({"worker": w}, round(v, 6))
          for w, v in sorted(worker_compute.items())))
    if max_wait_share is not None:
        lines.append("# TYPE stpu_max_wait_share gauge")
        lines.append(f"stpu_max_wait_share {max_wait_share}")
    # Continuous-profiler families (schema v13): the same ``stpu_prof_*``
    # names ``prometheus_prof_lines`` serves live, reconstructed from
    # the stream's last snapshot per (engine, program key).
    emit("stpu_prof_sampled_total", "counter",
         (({"engine": e}, n) for e, n in sorted(prof_sampled.items())))
    for metric, field in (("stpu_prof_flops", "flops"),
                          ("stpu_prof_bytes", "bytes"),
                          ("stpu_prof_flops_per_s", "flops_per_s"),
                          ("stpu_prof_bytes_per_s", "bytes_per_s"),
                          ("stpu_prof_intensity", "intensity"),
                          ("stpu_prof_cost_ratio", "cost_ratio"),
                          ("stpu_prof_measured_seconds", "measured_s")):
        emit(metric, "gauge",
             (({"engine": e, "key": k}, v)
              for (e, k), evt in sorted(prof_finals.items())
              for v in (evt.get(field),)
              if isinstance(v, (int, float))))
    # Latency histograms (schema v11): the final snapshot per run is
    # the whole distribution — _bucket/_sum/_count via the same
    # emission helper the live ``GET /.metrics`` uses, so a dead
    # capture and a live scrape read identically. Merged across runs
    # by series identity (keys carry their engine/worker labels).
    if hist_finals:
        from stateright_tpu.obs.hist import prometheus_hist_lines

        merged: Dict[str, dict] = {}
        for run in sorted(hist_finals):
            for key, data in hist_finals[run].items():
                cur = merged.get(key)
                # A rotated producer (migration) re-emits the same
                # series under a new run id with LARGER cumulative
                # counts — keep the superset.
                if cur is None or (data.get("count", 0)
                                   >= cur.get("count", 0)):
                    merged[key] = data
        lines += prometheus_hist_lines(merged)
    return "\n".join(lines) + ("\n" if lines else "")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="export an STpu_TRACE JSONL capture to a "
                    "Perfetto-loadable Chrome trace and/or a Prometheus "
                    "text dump")
    ap.add_argument("path", help="JSONL trace file")
    ap.add_argument("-o", "--out", default=None,
                    help="Chrome trace output path (default "
                         "<path>.chrome.json)")
    ap.add_argument("--prom", default=None,
                    help="also write a Prometheus text dump here")
    ap.add_argument("--no-chrome", action="store_true",
                    help="skip the Chrome trace output")
    args = ap.parse_args(argv)

    events = load_events(args.path)
    if not events:
        print(f"no events in {args.path}", file=sys.stderr)
        return 1
    if not args.no_chrome:
        out = args.out or args.path + ".chrome.json"
        with open(out, "w", encoding="utf-8") as f:
            json.dump(to_chrome(events), f)
        print(f"wrote {out} ({len(events)} events)")
    if args.prom:
        with open(args.prom, "w", encoding="utf-8") as f:
            f.write(to_prometheus(events))
        print(f"wrote {args.prom}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
