#!/usr/bin/env python
"""Validates a JSONL telemetry stream against the obs schema.

Accepts an ``STpu_TRACE`` capture (``run_start`` / ``wave`` /
``span`` / ``counter`` / ``gauge`` / ``grow`` / ``overflow_redispatch``
/ ``run_end`` / ... events).

Used by the tier-1 suite (``tests/test_obs_trace.py``) and runnable
standalone::

    python tools/trace_lint.py trace.jsonl            # exit 1 on errors
    python tools/trace_lint.py --quiet trace.jsonl    # summary only

Beyond per-line schema validation it checks these stream-level
invariants: wave indices are contiguous per run, cumulative
``states``/``unique`` never decrease within a run (a truncated or
interleaved-corrupt file trips these even when every line parses),
every ``fault`` event (an ``STpu_FAULTS`` injection firing, or an
observed failure) is eventually followed by a ``recover``/``retry`` or
a terminal ``abort`` — an unrecovered fault at end-of-stream is
exactly the silent-death mode the resilience subsystem exists to rule
out — and the membership invariant (schema v4): every ``worker_lost``
is eventually followed by a ``migrate_done`` or a terminal ``abort``,
so a lost worker whose partitions were never rebuilt anywhere cannot
pass a lint.

Schema v5 (the merged distributed stream) adds three more: per-worker
``seq`` values are strictly increasing in file order (the collector's
merge contract — ``seq`` never resets, even across the migration
tracer-run rotation, so this check spans rotations); every
``elastic_worker`` wave event carries its ``worker``/``seq``/``round``
attribution and every ``elastic`` coordinator wave its
``epoch``/``round``; and faults that name a ``worker`` pair PER
WORKER — a worker-tagged fault is retired by the ``migrate_done``
that rebuilds that worker's partitions (matched through its
``worker_lost``), not by whichever recovery happens to come first, so
two concurrent casualties cannot retire each other's faults. Flight-
recorder postmortem dumps (``obs/flight.py``) are valid input too —
their ``postmortem`` header is schema v5.

Schema v8 (the single-kernel wave) adds only nullable wave fields
(``kernel_path``/``rows``) — no new stream invariant; the field-set
exactness check picks them up through the versioned field map.

Schema v9 (cross-job wave multiplexing) adds the per-run attribution
window: a mux TOTAL wave (``job_id`` null, ``jobs_in_wave`` = J) must
be followed by exactly J attributed waves (``job_id`` set, same
``jobs_in_wave``) whose ``successors``/``candidates``/``novel`` deltas
sum to the total's, before the next total, any solo wave, or the run's
end — per-job attribution that doesn't add up to the device dispatch
is fabricated accounting. Attributed waves with NO open window are
fine: a per-JOB trace file carries only its own tenant's attributed
lines (its deltas sum across files, not within one).

Schema v10 (asynchronous host I/O) adds the checkpoint-generation
pairing: every ``ckpt_begin`` is eventually followed by a ``ckpt_done``
(retired oldest-first within its run — the writer is FIFO), or
explained by a ``fault``/``abort`` (a background write that died
surfaces at the next safe point, so the begin it interrupted is
accounted for, not silent). A run must not END with a generation still
open — judged at end-of-stream, not at the ``run_end`` itself, because
fault and Supervisor events ride their own tracers (own run ids, own
flush buffers) and can land in the merged file on either side of the
begin they explain. Additionally each run's summed ``io_stall_s`` wave
gauge must fit
inside its ``run_end`` duration window — stall seconds are wall-clock
subsets of the run, so a sum exceeding the run length is fabricated
accounting.

Schema v11 (service-level observability) adds the histogram-snapshot
invariants: ``hist_snapshot`` events are cumulative-by-construction,
so per run the ``snap`` index strictly increases, and per
(run, series) the non-cumulative bucket counts must sum exactly to the
series ``count`` while ``count`` and ``sum`` are monotone
non-decreasing across snapshots (a shrinking histogram is a truncated
or re-ordered stream — real histograms only ever accumulate). These
checks hold in postmortem dumps too: a ring window may DROP snapshots,
but the survivors still only grow.

Schema v13 (the continuous wave profiler) adds the profile-snapshot
invariants: per run the ``snap`` ordinal strictly increases (sampling
is a per-producer counter, so a reordered or interleaved-corrupt merge
trips it — in postmortem dumps too, where a ring may DROP snapshots
but never reorders them); every snapshot's ``measured_s`` and
``cost_ratio`` are finite and positive (the ratio is defined against
the program's own first sampled baseline, which makes a non-finite or
non-positive value fabricated by construction); and where a snapshot
carries both ``flops`` and ``bytes``, its ``intensity`` gauge must be
their quotient to rounding — roofline coordinates that disagree with
their own cost model are fabricated accounting. Wave events gain the
nullable ``cost_flops``/``cost_bytes``/``cost_ratio`` fields, picked
up by the versioned field-set exactness check; v12 and older captures
still lint under their own field maps.

Schema v7 (the job service) adds the per-job pairing invariant: every
``job_submit`` is eventually followed by a ``job_done`` or
``job_abort`` carrying the SAME ``job`` id — unlike the fault pairing
this one has an exact join key, so concurrent jobs in one stream can
never retire each other's submissions. A stream that ends with a job
neither finished nor acknowledged (preempt/failure) lost work.

Schema v14 (the overload controller) adds the control-stream
invariants: every ``shed`` carries a machine-readable ``reason`` from
the declared vocabulary and a positive ``retry_after_s`` (a shed the
operator can't attribute, or a 429 with no honest retry hint, is a
policy decision the stream failed to explain); every ``park`` is
eventually followed by a ``resume`` or a terminal ``job_abort`` for
the SAME job id (exact join key — parked work is work the controller
OWES back, and a stream that ends still holding a park lost it), and a
``resume`` must name a ``resumed_as`` continuation distinct from the
parked job; ``controller`` brownout-ladder events are edge-triggered —
consecutive events in one run must CHANGE ``rung`` (a repeated rung is
level-triggered spam), with ``kept`` equal to the ``rung`` actually
reported and never exceeding ``requested`` (the round-10
requested/kept honesty rule applied to degradation steps).

Schema v17 (the chunked probe) adds ``probe_slots`` to wave events,
null exactly where ``probe_rounds`` is and never below it: every
counted round carries at least one row.

Schema v18 (the sized exchange buckets) adds ``exchange_rounds`` to
wave events, null exactly where ``exchange_slots`` is and never below
``waves``: every wave of an exchanging dispatch runs at least one
round.

Schema v20 (the chunked append) adds ``append_slots`` to wave events,
null exactly where ``probe_slots`` is and never negative.

Schema v6 (the tiered state store) adds three more: every FRONTIER
``spill`` is eventually followed by a ``page_in`` or the producing
run's end (a stream that stops with paged-out frontier blocks
outstanding lost work); per-run per-tier byte gauges
(``tier_*_bytes`` on wave events) are monotone non-decreasing between
``pressure`` resets; and the host-store producers (host BFS/DFS, the
elastic runtime) must carry real ``capacity``/``load_factor``/
``out_rows`` occupancy gauges — the permanent-null allowance is
withdrawn for v6+ captures. v5 and older captures still lint under
their own rules.

Dependency-free beyond ``stateright_tpu.obs.schema`` (no jax, no
backend init) — safe to run while another process holds the chip.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
from typing import Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from stateright_tpu.obs.schema import (SCHEMA_VERSION,  # noqa: E402
                                       SHED_REASONS, validate_event)


def _too_new(obj) -> bool:
    """An event stamped by a NEWER schema than this validator knows.
    ``validate_event`` reports it with one clear upgrade message (no
    field-set mismatch cascade); the stream-invariant checks skip such
    events too — their field semantics may have changed."""
    ver = obj.get("schema_version") if isinstance(obj, dict) else None
    return isinstance(ver, int) and ver > SCHEMA_VERSION


def lint_lines(lines) -> Tuple[Dict[str, int], List[str]]:
    """Validates an iterable of JSONL lines; returns
    ``(counts_by_kind, errors)``. ``counts_by_kind`` tallies event
    types, plus a ``runs`` entry."""
    counts: Dict[str, int] = {}
    errors: List[str] = []
    last_wave: Dict[str, int] = {}
    last_counts: Dict[str, Tuple[int, int]] = {}
    runs = set()
    # Resilience pairing: faults awaiting a later recover/retry/abort.
    # A recover (or a supervisor retry record, schema v4) retires the
    # oldest outstanding fault (one recovery per failure); a terminal
    # abort retires every outstanding fault (the supervisor gave up —
    # the stream ends acknowledged, not silent). Recoveries with no
    # preceding fault are fine: organic failures (no injection)
    # recover through the same path. Deliberately STREAM-GLOBAL, not
    # per run: a fault fires inside an engine run while its recovery
    # is emitted by the SUPERVISOR's (or the bench parent's) own
    # tracer — different run ids by construction, so there is no join
    # key. The cost is a known approximation: with two concurrent
    # supervised runs in one file, one run's recover can retire the
    # other's fault. The membership invariant works the same way:
    # worker_lost events await a later migrate_done (or the terminal
    # abort) — a lost worker whose partitions never landed anywhere is
    # an unrecovered loss.
    open_faults: List[Tuple[int, str]] = []
    open_losses: List[Tuple[int, str]] = []
    # v5: faults that NAME a worker pair per worker — retired by the
    # migrate_done that follows that worker's worker_lost (matched
    # below), by a recover/retry when no loss was ever observed (the
    # in-engine degradation path), or by the terminal abort.
    worker_faults: Dict[str, List[int]] = {}
    # v5: per-worker seq monotonicity, spanning run rotations.
    last_seq: Dict[str, Tuple[int, int]] = {}
    # v6 (tiered store): frontier spills awaiting a page_in (or the
    # producing run's end — a run that finishes with blocks still cold
    # simply never needed them again); per-(run, tier) byte gauges
    # must be monotone BETWEEN pressure resets (a pressure event marks
    # a legitimate shrink — page-in consumption, warm->disk pushes).
    open_spills: Dict[str, List[int]] = {}
    # v7 (job service): submits awaiting their job_done/job_abort.
    # Exact-keyed by the job id — no oldest-first approximation here.
    open_jobs: Dict[str, int] = {}
    # v14 (overload control): parks awaiting their resume (or a
    # terminal job_abort for the same id) — exact-keyed like v7; and
    # per-run last controller rung for the edge-trigger check.
    open_parks: Dict[str, int] = {}
    last_ctrl_rung: Dict[str, Tuple[int, int]] = {}
    # v9 (wave multiplexing): per-run open attribution window — the
    # mux TOTAL wave awaiting its jobs_in_wave attributed lines.
    mux_windows: Dict[str, dict] = {}
    # v10 (async host I/O): checkpoint generations begun but not yet
    # landed, per run (the writer is FIFO, so ckpt_done retires the
    # oldest). A fault/abort excuses them stream-wide — the same known
    # approximation as the fault pairing itself: the begin a dying
    # write interrupted has no join key to the fault that explains it.
    # The excuse is also flush-order-independent: fault events ride
    # their own tracer (own run id, own buffer), so in the merged file
    # a fault can land BEFORE the begin it killed — begins left open at
    # run_end are therefore deferred and judged only at end-of-stream,
    # once the whole stream has had its say.
    open_ckpts: Dict[str, List[int]] = {}
    lost_ckpts: List[Tuple[int, str, int]] = []
    ckpt_excused = False
    # v10: per-run summed io_stall_s, checked against run_end's dur.
    io_stall_sums: Dict[str, float] = {}
    # v11 (service observability): per-run last snap index, and per
    # (run, series) last (count, sum) — histograms only ever grow.
    last_snap: Dict[str, Tuple[int, int]] = {}
    last_hist: Dict[Tuple[str, str], Tuple[int, int, float]] = {}
    # v13 (continuous profiler): per-run last profile_snapshot ordinal.
    last_prof_snap: Dict[str, Tuple[int, int]] = {}
    ended_runs = set()
    last_tier_bytes: Dict[Tuple[str, str], Tuple[int, int]] = {}
    # A flight-recorder postmortem (first event: the ``postmortem``
    # header) is a bounded WINDOW onto a failure, not a complete
    # stream: wave indices may start mid-run and stop abruptly,
    # cumulative counts may straddle a rollback, and an unretired
    # fault at end-of-file is the file's entire reason to exist — so
    # dumps keep per-line schema validation and per-worker seq order,
    # but relax contiguity/backwards-counts to per-run monotonicity
    # and skip the end-of-stream pairing errors.
    dump_mode = False
    first_event = True
    for lineno, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        try:
            obj = json.loads(line)
        except ValueError as e:
            errors.append(f"line {lineno}: invalid JSON: {e}")
            continue
        for err in validate_event(obj):
            errors.append(f"line {lineno}: {err}")
        if not isinstance(obj, dict):
            continue
        if first_event:
            dump_mode = obj.get("type") == "postmortem"
            first_event = False
        kind = obj.get("type")
        counts[kind] = counts.get(kind, 0) + 1
        run = obj.get("run")
        if run:
            runs.add(run)
        if _too_new(obj):
            continue
        etype = obj.get("type")
        # v5 per-worker seq monotonicity: any event carrying both a
        # worker and a seq (the relayed streams) must only ever move
        # forward — seq survives run rotation precisely so this check
        # can span migrations.
        seq, worker_id = obj.get("seq"), obj.get("worker")
        if isinstance(seq, int) and isinstance(worker_id, str):
            prev_line, prev_seq = last_seq.get(worker_id, (0, None))
            if prev_seq is not None and seq <= prev_seq:
                errors.append(
                    f"line {lineno}: worker {worker_id!r}: seq {seq} "
                    f"after seq {prev_seq} (line {prev_line}) — "
                    "per-worker order lost in the merge")
            last_seq[worker_id] = (lineno, seq)
        if etype == "fault":
            fw = obj.get("worker")
            if isinstance(fw, str):
                worker_faults.setdefault(fw, []).append(lineno)
            else:
                open_faults.append((lineno, str(obj.get("point"))))
            # v10: a fault explains begun-but-unlanded generations (the
            # background write it killed never emits its ckpt_done).
            open_ckpts.clear()
            ckpt_excused = True
        elif etype in ("recover", "retry"):
            if open_faults:
                open_faults.pop(0)
            else:
                # No anonymous fault outstanding: a recovery may
                # retire the oldest worker-tagged fault whose loss was
                # never observed (in-engine recovery paths).
                for fw in sorted(worker_faults):
                    if worker_faults[fw]:
                        worker_faults[fw].pop(0)
                        break
        elif etype == "worker_lost":
            open_losses.append((lineno, str(obj.get("worker"))))
        elif etype == "migrate_done":
            if open_losses:
                _, lost_worker = open_losses.pop(0)
                # The per-worker pairing: rebuilding the lost worker's
                # partitions is what retires ITS fault, whichever
                # epoch/rotation the events straddle.
                if worker_faults.get(lost_worker):
                    worker_faults[lost_worker].pop(0)
        elif etype == "abort":
            open_faults.clear()
            open_losses.clear()
            worker_faults.clear()
            open_spills.clear()
            open_ckpts.clear()
            ckpt_excused = True
        elif etype == "ckpt_begin":
            if isinstance(run, str):
                open_ckpts.setdefault(run, []).append(lineno)
        elif etype == "ckpt_done":
            if isinstance(run, str) and open_ckpts.get(run):
                open_ckpts[run].pop(0)
        elif etype == "spill":
            if obj.get("kind") == "frontier" and isinstance(run, str):
                # Only paged-out FRONTIER blocks owe a page_in: visited
                # spills are membership-only and never come back up.
                open_spills.setdefault(run, []).append(lineno)
        elif etype == "page_in":
            if isinstance(run, str) and open_spills.get(run):
                open_spills[run].pop(0)
        elif etype == "job_submit":
            job = obj.get("job")
            if isinstance(job, str):
                if job in open_jobs:
                    errors.append(
                        f"line {lineno}: job {job!r} submitted again at "
                        f"line {lineno} while its submit at line "
                        f"{open_jobs[job]} is still unresolved")
                open_jobs[job] = lineno
        elif etype in ("job_done", "job_abort"):
            job = obj.get("job")
            if isinstance(job, str):
                open_jobs.pop(job, None)
                if etype == "job_abort":
                    # v14: a terminal abort is a legitimate end for a
                    # parked job (shutdown before pressure cleared).
                    open_parks.pop(job, None)
        elif etype == "shed":
            reason = obj.get("reason")
            if reason not in SHED_REASONS:
                errors.append(
                    f"line {lineno}: shed with reason {reason!r} — "
                    f"every shed must carry one of {SHED_REASONS} "
                    "(an unattributable 429 is a policy decision the "
                    "stream failed to explain)")
            ra = obj.get("retry_after_s")
            if not (isinstance(ra, (int, float)) and ra > 0
                    and math.isfinite(ra)):
                errors.append(
                    f"line {lineno}: shed with retry_after_s {ra!r} — "
                    "a 429 must carry a positive, finite retry hint")
        elif etype == "park":
            job = obj.get("job")
            if isinstance(job, str):
                if job in open_parks:
                    errors.append(
                        f"line {lineno}: job {job!r} parked again "
                        f"while its park at line {open_parks[job]} is "
                        "still unresolved")
                open_parks[job] = lineno
        elif etype == "resume":
            job = obj.get("job")
            if isinstance(job, str):
                open_parks.pop(job, None)
            resumed_as = obj.get("resumed_as")
            if not isinstance(resumed_as, str) or resumed_as == job:
                errors.append(
                    f"line {lineno}: resume of {job!r} with "
                    f"resumed_as {resumed_as!r} — the continuation "
                    "must be a distinct job id")
        elif etype == "controller":
            rung, requested, kept = (obj.get("rung"),
                                     obj.get("requested"),
                                     obj.get("kept"))
            if isinstance(kept, int):
                if isinstance(requested, int) and kept > requested:
                    errors.append(
                        f"line {lineno}: controller kept {kept} > "
                        f"requested {requested} — kept can only "
                        "honestly report what was clamped DOWN")
                if isinstance(rung, int) and kept != rung:
                    errors.append(
                        f"line {lineno}: controller rung {rung} != "
                        f"kept {kept} — the reported rung IS the kept "
                        "outcome")
            if isinstance(rung, int) and isinstance(run, str):
                prev = last_ctrl_rung.get(run)
                if prev is not None and prev[1] == rung:
                    errors.append(
                        f"line {lineno}: run {run}: controller event "
                        f"repeats rung {rung} (last at line {prev[0]}) "
                        "— ladder transitions are edge-triggered")
                last_ctrl_rung[run] = (lineno, rung)
        elif etype == "hist_snapshot":
            # v11: snapshots are cumulative since the producer armed —
            # snap strictly increases per run; per (run, series) the
            # non-cumulative buckets sum exactly to count, and
            # count/sum never shrink (histograms only accumulate).
            # Dumps keep these checks: a ring may drop snapshots, but
            # the survivors still only grow.
            hists = obj.get("hists")
            snap = obj.get("snap")
            if isinstance(run, str) and isinstance(snap, int):
                prev = last_snap.get(run)
                if prev is not None and snap <= prev[1]:
                    errors.append(
                        f"line {lineno}: run {run}: hist_snapshot "
                        f"snap {snap} after snap {prev[1]} (line "
                        f"{prev[0]}) — snapshot order lost")
                last_snap[run] = (lineno, snap)
            if isinstance(run, str) and isinstance(hists, dict):
                for key in sorted(hists):
                    data = hists[key]
                    if not isinstance(data, dict):
                        errors.append(
                            f"line {lineno}: run {run}: series "
                            f"{key!r} payload is not an object")
                        continue
                    buckets = data.get("buckets")
                    count = data.get("count")
                    hsum = data.get("sum")
                    if (isinstance(buckets, list)
                            and isinstance(count, int)):
                        bsum = sum(b for b in buckets
                                   if isinstance(b, int))
                        if bsum != count:
                            errors.append(
                                f"line {lineno}: run {run}: series "
                                f"{key!r}: buckets sum to {bsum}, "
                                f"count says {count} — snapshot is "
                                "internally inconsistent")
                    prev = last_hist.get((run, key))
                    if prev is not None:
                        if isinstance(count, int) and count < prev[1]:
                            errors.append(
                                f"line {lineno}: run {run}: series "
                                f"{key!r}: count went backwards "
                                f"({prev[1]}->{count}, last at line "
                                f"{prev[0]})")
                        if (isinstance(hsum, (int, float))
                                and hsum < prev[2] - 1e-6):
                            errors.append(
                                f"line {lineno}: run {run}: series "
                                f"{key!r}: sum went backwards "
                                f"({prev[2]}->{hsum}, last at line "
                                f"{prev[0]})")
                    last_hist[(run, key)] = (
                        lineno,
                        count if isinstance(count, int) else 0,
                        float(hsum) if isinstance(hsum, (int, float))
                        else 0.0)
        elif etype == "profile_snapshot":
            # v13: the sampling ordinal is a per-producer counter —
            # strictly increasing per run, in dumps too (a ring drops
            # snapshots but never reorders them).
            snap = obj.get("snap")
            if isinstance(run, str) and isinstance(snap, int):
                prev = last_prof_snap.get(run)
                if prev is not None and snap <= prev[1]:
                    errors.append(
                        f"line {lineno}: run {run}: profile_snapshot "
                        f"snap {snap} after snap {prev[1]} (line "
                        f"{prev[0]}) — snapshot order lost")
                last_prof_snap[run] = (lineno, snap)
            # v13: measured_s and cost_ratio are positive and finite by
            # construction (the ratio is against the program's own
            # first sampled baseline) — anything else is fabricated.
            for field in ("measured_s", "cost_ratio"):
                val = obj.get(field)
                if (isinstance(val, (int, float))
                        and not isinstance(val, bool)
                        and (not math.isfinite(val) or val <= 0)):
                    errors.append(
                        f"line {lineno}: profile_snapshot {field} "
                        f"{val!r} is not finite and positive — "
                        "fabricated against the program's own "
                        "baseline")
            # v13: roofline coordinates must agree with their own cost
            # model (intensity = flops / bytes, to rounding).
            flops, byts, inten = (obj.get("flops"), obj.get("bytes"),
                                  obj.get("intensity"))
            if (isinstance(flops, (int, float))
                    and isinstance(byts, (int, float)) and byts > 0
                    and isinstance(inten, (int, float))):
                want = flops / byts
                if abs(inten - want) > max(1e-5, 1e-3 * abs(want)):
                    errors.append(
                        f"line {lineno}: profile_snapshot intensity "
                        f"{inten} disagrees with flops/bytes "
                        f"{want:.6f} — roofline coordinates are "
                        "fabricated")
        elif etype == "pressure":
            # A legitimate tier shrink: reset the monotonicity window
            # for this run's tier.
            if isinstance(run, str):
                last_tier_bytes.pop((run, str(obj.get("tier"))), None)
        elif etype == "run_end" and isinstance(run, str):
            ended_runs.add(run)
            win = mux_windows.pop(run, None)
            if win is not None and not dump_mode:
                errors.append(
                    f"line {lineno}: run {run}: run_end with the mux "
                    f"wave total at line {win['line']} still awaiting "
                    f"{win['remaining']} attributed line(s)")
            # v10: a run must not end with a checkpoint generation
            # begun but never landed (nor explained by a fault/abort).
            # Deferred rather than judged here: the fault that explains
            # this begin may flush to the file AFTER (or before) this
            # run_end, since Supervisor/fault events ride other runs'
            # buffers — end-of-stream decides.
            for begin_line in open_ckpts.pop(run, []):
                lost_ckpts.append((lineno, run, begin_line))
            # v10: summed per-wave io_stall_s must fit inside the
            # run's wall-clock window (slack covers rounding and the
            # final checkpoint landing after the last wave event).
            dur = obj.get("dur")
            stall = io_stall_sums.pop(run, 0.0)
            if (isinstance(dur, (int, float)) and not dump_mode
                    and stall > dur + max(0.1, 0.05 * dur)):
                errors.append(
                    f"line {lineno}: run {run}: summed io_stall_s "
                    f"{stall:.3f}s exceeds the run_end duration "
                    f"window {dur:.3f}s — stall accounting is "
                    "fabricated")
        if etype == "wave" and isinstance(run, str):
            idx = obj.get("wave")
            if isinstance(idx, int):
                if dump_mode:
                    # A ring window: indices may start anywhere, must
                    # still move forward per run.
                    prev = last_wave.get(run)
                    if prev is not None and idx <= prev:
                        errors.append(
                            f"line {lineno}: run {run}: wave index "
                            f"{idx} after {prev} (dump reorder)")
                else:
                    expect = last_wave.get(run, -1) + 1
                    if idx != expect:
                        errors.append(
                            f"line {lineno}: run {run}: wave index "
                            f"{idx}, expected {expect} (stream gap or "
                            "reorder)")
                last_wave[run] = idx
            stall = obj.get("io_stall_s")
            if isinstance(stall, (int, float)):
                io_stall_sums[run] = io_stall_sums.get(run, 0.0) + stall
            states, unique = obj.get("states"), obj.get("unique")
            if isinstance(states, int) and isinstance(unique, int):
                ps, pu = last_counts.get(run, (0, 0))
                if (states < ps or unique < pu) and not dump_mode:
                    errors.append(
                        f"line {lineno}: run {run}: cumulative counts "
                        f"went backwards (states {ps}->{states}, "
                        f"unique {pu}->{unique})")
                last_counts[run] = (states, unique)
            # v5 attribution requirements: relayed worker waves must
            # say WHO did the work and WHERE in the merge order they
            # belong; coordinator round summaries must be positioned
            # by (epoch, round). Older captures predate the keys.
            if (isinstance(obj.get("schema_version"), int)
                    and obj["schema_version"] >= 5):
                engine = obj.get("engine")
                if engine == "elastic_worker":
                    for field in ("worker", "seq", "round"):
                        if obj.get(field) is None:
                            errors.append(
                                f"line {lineno}: elastic_worker wave "
                                f"without {field!r} — unattributable "
                                "work in a merged stream")
                elif engine == "elastic":
                    for field in ("epoch", "round"):
                        if obj.get(field) is None:
                            errors.append(
                                f"line {lineno}: elastic coordinator "
                                f"wave without {field!r}")
            # v17: the probe's slots are counted where its rounds are,
            # and every round carries at least one row.
            rounds, slots = obj.get("probe_rounds"), obj.get("probe_slots")
            if (isinstance(obj.get("schema_version"), int)
                    and obj["schema_version"] >= 17
                    and ((rounds is None) != (slots is None)
                         or (isinstance(rounds, int)
                             and isinstance(slots, int)
                             and slots < rounds))):
                errors.append(
                    f"line {lineno}: wave probe_slots {slots!r} against "
                    f"probe_rounds {rounds!r}: each counted round "
                    "carries at least one row")
            # v18: the exchange's rounds are counted where its slots
            # are, and every wave runs at least one.
            x_rounds = obj.get("exchange_rounds")
            x_slots, waves = obj.get("exchange_slots"), obj.get("waves")
            if (isinstance(obj.get("schema_version"), int)
                    and obj["schema_version"] >= 18
                    and ((x_rounds is None) != (x_slots is None)
                         or (isinstance(x_rounds, int)
                             and isinstance(waves, int)
                             and x_rounds < waves))):
                errors.append(
                    f"line {lineno}: wave exchange_rounds {x_rounds!r} "
                    f"against exchange_slots {x_slots!r} and waves "
                    f"{waves!r}: each wave of an exchange runs at least "
                    "one round")
            # v20: the append's slots are counted where the probe's are.
            a_slots = obj.get("append_slots")
            if (isinstance(obj.get("schema_version"), int)
                    and obj["schema_version"] >= 20
                    and ((a_slots is None) != (slots is None)
                         or (isinstance(a_slots, int) and a_slots < 0))):
                errors.append(
                    f"line {lineno}: wave append_slots {a_slots!r} "
                    f"against probe_slots {slots!r}: the append's slots "
                    "are counted where the probe's are")
            # v9 attribution window (wave multiplexing): a TOTAL mux
            # wave (job_id null, jobs_in_wave set) opens a window that
            # exactly jobs_in_wave attributed lines must close, their
            # per-job deltas summing to the total's — short, long, or
            # interrupted attribution is fabricated accounting. An
            # attributed line with NO open window is legitimate (a
            # per-job trace file sees only its own tenant's lines).
            if (isinstance(obj.get("schema_version"), int)
                    and obj["schema_version"] >= 9
                    and isinstance(run, str) and not dump_mode):
                job_id = obj.get("job_id")
                jobs_in_wave = obj.get("jobs_in_wave")
                win = mux_windows.get(run)
                if job_id is None and isinstance(jobs_in_wave, int):
                    if win is not None:
                        errors.append(
                            f"line {lineno}: run {run}: new mux wave "
                            f"total while the total at line "
                            f"{win['line']} still awaits "
                            f"{win['remaining']} attributed line(s)")
                    mux_windows[run] = {
                        "line": lineno, "jobs": jobs_in_wave,
                        "remaining": jobs_in_wave,
                        "totals": tuple(obj.get(f) for f in
                                        ("successors", "candidates",
                                         "novel")),
                        "sums": [0, 0, 0]}
                elif job_id is not None and win is not None:
                    if jobs_in_wave != win["jobs"]:
                        errors.append(
                            f"line {lineno}: run {run}: attributed "
                            f"wave says jobs_in_wave={jobs_in_wave}, "
                            f"its total at line {win['line']} said "
                            f"{win['jobs']}")
                    for i, field in enumerate(("successors",
                                               "candidates", "novel")):
                        val = obj.get(field)
                        if isinstance(val, int):
                            win["sums"][i] += val
                    win["remaining"] -= 1
                    if win["remaining"] <= 0:
                        for i, field in enumerate(("successors",
                                                   "candidates",
                                                   "novel")):
                            total = win["totals"][i]
                            if (isinstance(total, int)
                                    and win["sums"][i] != total):
                                errors.append(
                                    f"line {lineno}: run {run}: "
                                    f"per-job {field} sum to "
                                    f"{win['sums'][i]}, the wave "
                                    f"total at line {win['line']} "
                                    f"said {total}")
                        del mux_windows[run]
                elif job_id is None and jobs_in_wave is None \
                        and win is not None:
                    errors.append(
                        f"line {lineno}: run {run}: solo wave inside "
                        f"an open mux window (total at line "
                        f"{win['line']} awaits {win['remaining']} "
                        "attributed line(s))")
            # v6 invariants (tiered store). Host-store producers must
            # carry REAL occupancy gauges (capacity/load_factor/
            # out_rows were permanent nulls through v5 — the
            # null-allowance is withdrawn for v6+ captures), and the
            # per-tier byte gauges may only grow between pressure
            # resets (a shrink without a pressure marker is a
            # truncated or re-ordered stream).
            if (isinstance(obj.get("schema_version"), int)
                    and obj["schema_version"] >= 6):
                if obj.get("engine") in ("host_bfs", "host_dfs",
                                         "elastic", "elastic_worker"):
                    for field in ("capacity", "load_factor",
                                  "out_rows"):
                        if obj.get(field) is None:
                            errors.append(
                                f"line {lineno}: {obj['engine']} wave "
                                f"with null {field!r} — host store "
                                "occupancy gauges are required from "
                                "schema v6")
                if isinstance(run, str):
                    for tier in ("device", "host", "disk"):
                        val = obj.get(f"tier_{tier}_bytes")
                        if not isinstance(val, int):
                            continue
                        key = (run, tier)
                        prev = last_tier_bytes.get(key)
                        if (prev is not None and val < prev[1]
                                and not dump_mode):
                            errors.append(
                                f"line {lineno}: run {run}: "
                                f"tier_{tier}_bytes went backwards "
                                f"({prev[1]}->{val}, last at line "
                                f"{prev[0]}) without a pressure "
                                "reset")
                        last_tier_bytes[key] = (lineno, val)
    if not dump_mode:
        for lineno, point in open_faults:
            errors.append(
                f"line {lineno}: fault {point!r} is never followed by "
                "a recover or terminal abort in the stream "
                "(unrecovered failure)")
        for lineno, worker in open_losses:
            errors.append(
                f"line {lineno}: worker_lost {worker!r} is never "
                "followed by a migrate_done or terminal abort in the "
                "stream (lost partitions were never rebuilt)")
        for worker in sorted(worker_faults):
            for lineno in worker_faults[worker]:
                errors.append(
                    f"line {lineno}: fault on worker {worker!r} is "
                    "never followed by that worker's migration (or a "
                    "recover/terminal abort) in the stream "
                    "(unrecovered worker failure)")
        # v7: every submitted job must leave the stream finished or
        # acknowledged — an unpaired submit is work the service lost.
        for job, lineno in sorted(open_jobs.items(),
                                  key=lambda kv: kv[1]):
            errors.append(
                f"line {lineno}: job_submit {job!r} is never followed "
                "by a job_done or job_abort in the stream (the service "
                "lost the job)")
        # v14: parked work is work the controller OWES back — a stream
        # that ends still holding a park lost it.
        for job, lineno in sorted(open_parks.items(),
                                  key=lambda kv: kv[1]):
            errors.append(
                f"line {lineno}: park of {job!r} is never followed by "
                "a resume or terminal job_abort in the stream (the "
                "controller lost the parked job)")
        # v9: a mux wave total still awaiting attributed lines at
        # end-of-stream means the device dispatch's per-job split was
        # never accounted for.
        for run, win in sorted(mux_windows.items(),
                               key=lambda kv: kv[1]["line"]):
            errors.append(
                f"line {win['line']}: run {run}: mux wave total is "
                f"never followed by its {win['jobs']} attributed "
                f"line(s) (stream ends with {win['remaining']} "
                "outstanding)")
        # v10: a generation begun but never landed at end-of-stream is
        # a write the process lost track of — exactly the async-I/O
        # failure mode the safe-point join exists to rule out. Any
        # fault/abort anywhere in the stream excuses them (the same
        # stream-global approximation the fault branch applies, made
        # flush-order-independent).
        if not ckpt_excused:
            for end_line, run, begin_line in lost_ckpts:
                errors.append(
                    f"line {end_line}: run {run}: run_end with the "
                    f"ckpt_begin at line {begin_line} never landed "
                    "(no ckpt_done, no fault/abort explaining it)")
            for run, linenos in sorted(open_ckpts.items()):
                for begin_line in linenos:
                    errors.append(
                        f"line {begin_line}: run {run}: ckpt_begin is "
                        "never followed by a ckpt_done (or a "
                        "fault/abort explaining it) in the stream "
                        "(lost background write)")
        # v6: a paged-out frontier block must come back (page_in) or
        # the producing run must END — a stream that just stops with
        # cold frontier blocks outstanding lost work.
        for run, linenos in sorted(open_spills.items()):
            if run in ended_runs:
                continue
            for lineno in linenos:
                errors.append(
                    f"line {lineno}: run {run}: frontier spill is "
                    "never followed by a page_in or the run's end "
                    "(paged-out frontier blocks were lost)")
    counts["runs"] = len(runs)
    return counts, errors


def lint_file(path: str) -> Tuple[Dict[str, int], List[str]]:
    with open(path, encoding="utf-8") as f:
        return lint_lines(f)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="validate a JSONL telemetry stream (an STpu_TRACE "
                    "capture) against the obs schema")
    ap.add_argument("path", help="JSONL file to validate")
    ap.add_argument("--quiet", action="store_true",
                    help="suppress individual errors (summary only)")
    ap.add_argument("--max-errors", type=int, default=20,
                    help="errors to print before truncating (default 20)")
    args = ap.parse_args(argv)

    counts, errors = lint_file(args.path)
    total = sum(v for k, v in counts.items() if k != "runs")
    if not args.quiet:
        for err in errors[:args.max_errors]:
            print(err, file=sys.stderr)
        if len(errors) > args.max_errors:
            print(f"... and {len(errors) - args.max_errors} more",
                  file=sys.stderr)
    breakdown = ", ".join(f"{k}={v}" for k, v in sorted(counts.items()))
    if errors:
        print(f"FAIL: {len(errors)} error(s) in {total} event(s) "
              f"({breakdown})")
        return 1
    print(f"OK: {total} event(s) valid ({breakdown})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
