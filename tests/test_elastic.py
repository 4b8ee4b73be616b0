"""Elastic multi-worker sharding: migration/rebalance bit-identity,
membership + ownership units, per-shard checkpoint round-trips, and
the membership lint invariant.

The load-bearing suites are the bit-identity pins: a 2-worker elastic
run that LOSES a worker mid-run (migration: rollback to the newest
per-shard generation + rendezvous adoption + epoch bump) and a run
that GAINS a worker mid-run (rebalance at a drained barrier) must both
finish with totals — state count, unique count, discovery set, final
checkpoint payload — bit-identical to an unfaulted single-process
sharded run of the same model. The fast tier runs the in-process
(thread-transport) runtime on 2pc; the OS-process transport and the
paxos 16,668 matrix ride in ``-m slow`` (conftest budget guard).

Expensive runs are computed once at module scope and shared across the
assertions that read them (totals, lifecycle events, trace lint,
checkpoint payload), so the fast tier pays for each scenario once.
"""

import json
import os
import sys
from functools import partial

import numpy as np
import pytest

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "examples"))
sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    "tools"))

from two_phase_commit import TwoPhaseSys  # noqa: E402

from stateright_tpu.checkpoint_format import (CKPT_VERSION,  # noqa: E402
                                              load_checkpoint,
                                              make_header, shard_path,
                                              validate_header,
                                              verify_file, write_atomic)
from stateright_tpu.resilience import (ElasticChecker,  # noqa: E402
                                       Membership, OwnerMap,
                                       reset_fault_plans)

RMS = 3
WANT_STATES, WANT_UNIQUE = 1146, 288


def _totals(c):
    return (c.state_count(), c.unique_state_count(),
            tuple(sorted(c.discoveries())))


#: lazily-built shared runs: scenario -> (checker, ckpt_path, trace).
_RUNS: dict = {}


def _sharded_reference(tmp_root):
    if "sharded" not in _RUNS:
        ckpt = str(tmp_root / "sharded.npz")
        c = TwoPhaseSys(RMS).checker().spawn_tpu_bfs(
            batch_size=32, sharded=True, fused=False,
            checkpoint_path=ckpt).join()
        _RUNS["sharded"] = (c, ckpt, None)
    return _RUNS["sharded"]


def _elastic_run(tmp_root, scenario, **kwargs):
    if scenario not in _RUNS:
        ckpt = str(tmp_root / f"{scenario}.npz")
        trace = str(tmp_root / f"{scenario}.trace.jsonl")
        os.environ["STpu_TRACE"] = trace
        # Flight-recorder postmortems land beside the scenario's other
        # artifacts (worker_lost dumps are part of what the drills
        # assert).
        os.environ["STpu_FLIGHT_DIR"] = str(tmp_root)
        try:
            c = ElasticChecker(
                partial(TwoPhaseSys, RMS), workers=2, n_partitions=8,
                batch_rows=64, transport="thread",
                checkpoint_path=ckpt, checkpoint_every_rounds=2,
                **kwargs).join()
        finally:
            os.environ.pop("STpu_TRACE", None)
            os.environ.pop("STpu_FLIGHT_DIR", None)
        _RUNS[scenario] = (c, ckpt, trace)
    return _RUNS[scenario]


@pytest.fixture(scope="module")
def tmp_root(tmp_path_factory):
    return tmp_path_factory.mktemp("elastic")


# -- Bit-identity: clean / kill / join ------------------------------------

def test_elastic_clean_run_matches_single_process_sharded(tmp_root):
    ref, _, _ = _sharded_reference(tmp_root)
    c, _, _ = _elastic_run(tmp_root, "clean")
    assert _totals(c) == _totals(ref)
    assert c.state_count() == WANT_STATES
    assert c.unique_state_count() == WANT_UNIQUE
    assert c.epoch == 0 and not c.events


def test_elastic_kill_one_worker_bit_identical(tmp_root):
    """The acceptance drill: a 2-worker run loses one worker mid-run
    (simulated SIGKILL at round 4); membership turns it into
    worker_lost -> migration (rollback to the newest per-shard
    generation, rendezvous adoption, epoch bump) and the run completes
    bit-identical to the unfaulted single-process sharded run."""
    ref, _, _ = _sharded_reference(tmp_root)
    c, _, _ = _elastic_run(tmp_root, "kill", kill_at={4: "w1"})
    assert _totals(c) == _totals(ref)
    kinds = [e["type"] for e in c.events]
    assert kinds == ["worker_lost", "migrate_done"]
    assert c.events[0]["worker"] == "w1"
    # The survivor adopts exactly the dead worker's rendezvous share.
    w1_share = OwnerMap(8, ["w0", "w1"]).partitions_of("w1")
    assert c.events[1]["to"] == "w0"
    assert c.events[1]["partitions"] == len(w1_share) >= 1
    assert c.epoch == 1
    assert c.workers() == ["w0"]
    assert c.scheduler_stats()["elastic"]["migrations"] == 1


def test_elastic_join_one_worker_bit_identical(tmp_root):
    """A worker added mid-run triggers a logged rebalance (rendezvous
    handoff of the partitions it wins, via fresh per-shard snapshots at
    a drained barrier — no rollback) and the totals stay bit-identical
    to the unfaulted single-process sharded run."""
    ref, _, _ = _sharded_reference(tmp_root)
    c, _, _ = _elastic_run(tmp_root, "join", join_at={3: "w2"})
    assert _totals(c) == _totals(ref)
    kinds = [e["type"] for e in c.events]
    assert kinds == ["worker_join", "rebalance"]
    reb = c.events[1]
    assert reb["to"] == "w2" and 1 <= reb["partitions"] < 8
    assert c.epoch == 1
    assert sorted(c.workers()) == ["w0", "w1", "w2"]
    assert c.scheduler_stats()["elastic"]["rebalances"] == 1


def test_elastic_kill_trace_lints_clean(tmp_root):
    """The kill run's ONE merged trace passes trace_lint end to end —
    the v4 membership invariant (worker_lost eventually migrate_done),
    per-run wave monotonicity across the migration's tracer rotation,
    and the v5 distributed invariants (per-worker seq order, worker
    attribution on every relayed wave) — and contains every worker's
    own wave stream plus per-round straggler records."""
    import trace_lint

    _elastic_run(tmp_root, "kill", kill_at={4: "w1"})
    _, _, trace = _RUNS["kill"]
    counts, errors = trace_lint.lint_file(trace)
    assert not errors, errors[:5]
    assert counts.get("worker_lost", 0) == 1
    assert counts.get("migrate_done", 0) == 1
    assert counts.get("recover", 0) >= 1
    assert counts.get("wave", 0) > 0
    # The tentpole acceptance: the merged stream carries the workers'
    # OWN wave events (both of them — the casualty's last rounds
    # included), in causal (epoch, round, worker, seq) order, plus the
    # coordinator's summaries and straggler attribution.
    with open(trace, encoding="utf-8") as f:
        events = [json.loads(line) for line in f if line.strip()]
    waves = [e for e in events if e.get("type") == "wave"]
    by_worker = {}
    for w in waves:
        by_worker.setdefault(w.get("worker"), []).append(w)
    assert set(by_worker) >= {None, "w0", "w1"}  # None = coordinator
    for w in ("w0", "w1"):
        assert all(e["engine"] == "elastic_worker"
                   for e in by_worker[w])
        seqs = [e["seq"] for e in by_worker[w]]
        assert seqs == sorted(seqs)
        assert all(e["round"] is not None for e in by_worker[w])
    assert counts.get("straggler", 0) > 0


def test_elastic_join_trace_lints_clean(tmp_root):
    """The join drill's merged trace lints clean too — the joiner's
    relayed stream appears mid-file (its handoff reassignment rotates
    its run), and every one of the three workers is attributed."""
    import trace_lint

    _elastic_run(tmp_root, "join", join_at={3: "w2"})
    _, _, trace = _RUNS["join"]
    counts, errors = trace_lint.lint_file(trace)
    assert not errors, errors[:5]
    assert counts.get("worker_join", 0) == 1
    assert counts.get("rebalance", 0) == 1
    with open(trace, encoding="utf-8") as f:
        events = [json.loads(line) for line in f if line.strip()]
    workers = {e.get("worker") for e in events
               if e.get("type") == "wave"}
    assert workers >= {"w0", "w1", "w2"}


def test_elastic_kill_leaves_postmortem(tmp_root):
    """The always-on flight recorder's acceptance half: a killed
    worker leaves a postmortem. The casualty cannot dump its own ring
    (a SIGKILL has no exception handler), so the coordinator dumps ITS
    ring — which holds the merged recent events, the casualty's last
    relayed waves included — named for the casualty, and the
    worker_lost event carries the path."""
    c, _, _ = _elastic_run(tmp_root, "kill", kill_at={4: "w1"})
    lost = c.events[0]
    assert lost["type"] == "worker_lost"
    dump = lost.get("dump")
    assert dump and os.path.exists(dump)
    assert dump in c.elastic_obs()["postmortems"]
    with open(dump, encoding="utf-8") as f:
        lines = [json.loads(line) for line in f if line.strip()]
    assert lines[0]["type"] == "postmortem"
    assert "w1" in lines[0]["reason"]
    assert lines[0]["events"] == len(lines) - 1
    # The ring saw the casualty's own relayed waves.
    assert any(e.get("type") == "wave" and e.get("worker") == "w1"
               for e in lines[1:])


def test_elastic_obs_straggler_stats(tmp_root):
    """scheduler_stats()['elastic_obs']: per-worker straggler gauges
    aggregated from the round attributions — every round timed, both
    workers segmented, wait share a sane fraction, and the merge
    counters accounting for the relayed streams."""
    c, _, trace = _elastic_run(tmp_root, "kill", kill_at={4: "w1"})
    stats = c.scheduler_stats()
    obs = stats["elastic_obs"]
    # >= because every EXECUTED round is timed, while the round index
    # rewinds with the migration rollback.
    assert obs["rounds_timed"] >= stats["elastic"]["rounds"] > 0
    assert 0.0 <= obs["max_wait_share"] <= 1.0
    assert set(obs["workers"]) == {"w0", "w1"}
    for seg in obs["workers"].values():
        assert seg["waves"] > 0 and seg["compute_s"] >= 0.0
        assert 0.0 <= seg["wait_share"] <= 1.0
    assert sum(obs["slowest"].values()) == obs["rounds_timed"]
    assert obs["merged_events"] > 0 and obs["dropped_events"] == 0
    # The straggler events on the trace agree with the aggregate.
    with open(trace, encoding="utf-8") as f:
        stragglers = [json.loads(line) for line in f
                      if '"type":"straggler"' in line]
    assert len(stragglers) == obs["rounds_timed"]
    assert max(s["wait_share"] for s in stragglers) \
        == obs["max_wait_share"]


def test_elastic_metrics_endpoint(tmp_root):
    """GET /.metrics on an elastic checker: the straggler aggregates
    export as live per-worker Prometheus families (the aggregated
    view, read from running counters — no stream re-scan per
    scrape)."""
    from stateright_tpu.explorer import Explorer

    c, _, _ = _elastic_run(tmp_root, "kill", kill_at={4: "w1"})
    text = Explorer(c).metrics()
    assert "stpu_elastic_max_wait_share" in text
    assert 'stpu_elastic_worker_wait_share{worker="w0"}' in text
    assert 'stpu_elastic_worker_states_per_sec{worker="w1"}' in text
    assert "stpu_elastic_postmortems_total 1" in text
    # Round-19: the deprecated bare counter duals are gone.
    assert "stpu_elastic_postmortems 1" not in text
    assert f"stpu_states_total {c.state_count()}" in text


def test_trace_summary_cli_on_merged_trace(tmp_root):
    """tools/trace_summary.py smoke: the per-worker table renders from
    the kill drill's merged trace (and from the postmortem dump)."""
    import subprocess

    c, _, trace = _elastic_run(tmp_root, "kill", kill_at={4: "w1"})
    out = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "trace_summary.py"),
         trace], capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert "coordinator" in out.stdout
    assert "w0" in out.stdout and "w1" in out.stdout
    assert "wait%" in out.stdout
    # The postmortem dump is valid input too.
    dump = c.events[0]["dump"]
    out2 = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "tools", "trace_summary.py"),
         dump], capture_output=True, text=True, timeout=60)
    assert out2.returncode == 0, out2.stderr
    assert "w1" in out2.stdout


def test_elastic_final_checkpoint_payload_matches_sharded(tmp_root):
    """Checkpoint payload bit-identity: the elastic run's final
    generation (manifest counters + the union of the per-shard visited
    sections) equals the single-process sharded engine's final
    snapshot — same reachable set, same counters, both frontiers
    empty. Pinned on the MIGRATED run: redone work must not leak into
    the durable payload either."""
    ref, ref_ckpt, _ = _sharded_reference(tmp_root)
    c, ckpt, _ = _elastic_run(tmp_root, "kill", kill_at={4: "w1"})

    with load_checkpoint(ref_ckpt) as data:
        ref_visited = np.sort(np.asarray(data["visited"], np.uint64))
        assert len(np.asarray(data["pending_fps"])) == 0

    manifest = verify_file(ckpt)
    assert manifest["state_count"] == ref.state_count()
    assert manifest["unique_count"] == ref.unique_state_count()
    elastic_hdr = manifest["elastic"]
    assert elastic_hdr["partitions"] == 8

    shards = []
    for p in range(8):
        with load_checkpoint(shard_path(ckpt, p)) as data:
            header = validate_header(
                data, model_name="TwoPhaseSys", state_width=ref._W,
                use_symmetry=False, expect_shard=(p, 8))
            assert header["shard"]["round"] == elastic_hdr["round"]
            assert len(np.asarray(data["pending_fps"])) == 0
            shards.append(np.asarray(data["visited"], np.uint64))
    got = np.sort(np.concatenate(shards))
    assert got.shape == ref_visited.shape
    assert (got == ref_visited).all()


def test_elastic_injected_worker_crash_migrates(tmp_root, monkeypatch):
    """STpu_FAULTS=worker_crash: the registered fault point kills a
    worker at a deterministic coordinated round; the run migrates and
    stays bit-identical (fault -> recover pairing rides the same
    stream the supervisor uses)."""
    monkeypatch.setenv("STpu_FAULTS", "worker_crash@n=3")
    monkeypatch.setenv("STpu_FLIGHT_DIR", str(tmp_root / "crash-dumps"))
    os.makedirs(str(tmp_root / "crash-dumps"), exist_ok=True)
    reset_fault_plans()
    try:
        ckpt = str(tmp_root / "crash.npz")
        c = ElasticChecker(
            partial(TwoPhaseSys, RMS), workers=2, n_partitions=8,
            batch_rows=64, transport="thread", checkpoint_path=ckpt,
            checkpoint_every_rounds=2).join()
    finally:
        reset_fault_plans()
    assert (c.state_count(), c.unique_state_count()) == (WANT_STATES,
                                                         WANT_UNIQUE)
    assert [e["type"] for e in c.events] == ["worker_lost",
                                             "migrate_done"]
    # The dying worker dumped its OWN flight ring on the injected
    # fault (unlike a SIGKILL, an InjectedFault is catchable), and the
    # dump's LAST event is the fault point — the flight recorder's
    # whole job.
    victim = c.events[0]["worker"]
    dump = str(tmp_root / "crash-dumps"
               / f"stpu-postmortem-{victim}.jsonl")
    assert os.path.exists(dump)
    with open(dump, encoding="utf-8") as f:
        lines = [json.loads(line) for line in f if line.strip()]
    assert lines[0]["type"] == "postmortem"
    assert lines[-1]["type"] == "fault"
    assert lines[-1]["point"] == "worker_crash"
    assert lines[-1]["worker"] == victim


def test_elastic_resume_from_manifest(tmp_root):
    """The preemption story end to end: a completed run's manifest +
    shard files resume a FRESH coordinator (new workers, same
    generations) to the same totals — this is what a supervisor
    wrapping an elastic factory hands to the first retry."""
    c, ckpt, _ = _elastic_run(tmp_root, "clean")
    # resume_from and checkpoint_path are DIFFERENT stores: the resumed
    # run reads the old generations and writes its own fresh ones.
    resumed = ElasticChecker(
        partial(TwoPhaseSys, RMS), workers=2, n_partitions=8,
        batch_rows=64, transport="thread",
        checkpoint_path=str(tmp_root / "resumed-fresh.npz"),
        resume_from=ckpt).join()
    assert _totals(resumed) == _totals(c)
    assert os.path.exists(str(tmp_root / "resumed-fresh.npz"))
    # An explicit '...prev' manifest (what newest_valid_checkpoint
    # returns after a torn current write) also resumes: shard files are
    # probed beside the BASE path, and the matching .prev generations
    # are found by their recorded round.
    from stateright_tpu.checkpoint_format import PREV_SUFFIX
    assert os.path.exists(ckpt + PREV_SUFFIX)
    resumed_prev = ElasticChecker(
        partial(TwoPhaseSys, RMS), workers=2, n_partitions=8,
        batch_rows=64, transport="thread",
        checkpoint_path=str(tmp_root / "resumed-prev.npz"),
        resume_from=ckpt + PREV_SUFFIX).join()
    assert resumed_prev.unique_state_count() == c.unique_state_count()


# -- OwnerMap / Membership units ------------------------------------------

def test_owner_map_identity_and_remap():
    m = OwnerMap.identity(8)
    assert m.is_identity and m.epoch == 0
    assert [m.owner_of(p) for p in range(8)] == list(range(8))
    assert m.owner(17) == 17 % 8
    perm = [(i + 3) % 8 for i in range(8)]
    m2 = m.with_assignment(perm)
    assert m2.epoch == 1 and not m2.is_identity
    assert m2.owner(17) == perm[17 % 8]
    moves = m2.moves_from(m)
    assert len(moves) == 8  # a full rotation moves everything
    with pytest.raises(ValueError, match="owner"):
        OwnerMap(4, ["a"], assignment=["a", "b", "a", "a"])


def test_owner_map_rendezvous_minimal_migration():
    """The rendezvous property the migration cost rides on: losing a
    worker moves ONLY its partitions; a join moves ONLY partitions the
    joiner wins. Assignment is deterministic across processes."""
    m = OwnerMap(32, ["w0", "w1", "w2"])
    m_again = OwnerMap(32, ["w0", "w1", "w2"])
    assert m.assignment() == m_again.assignment()
    assert set(m.assignment()) == {"w0", "w1", "w2"}

    lost = m.with_owners(["w0", "w1"])  # w2 dies
    for p, (old, new) in lost.moves_from(m).items():
        assert old == "w2" and new in ("w0", "w1")
    assert set(lost.moves_from(m)) == set(m.partitions_of("w2"))

    joined = m.with_owners(["w0", "w1", "w2", "w3"])
    for p, (old, new) in joined.moves_from(m).items():
        assert new == "w3"
    assert joined.epoch == m.epoch + 1


def test_membership_lease_expiry():
    clock = [0.0]
    ms = Membership(lease_s=5.0, clock=lambda: clock[0])
    ms.add("w0")
    ms.add("w1")
    clock[0] = 4.0
    ms.beat("w1")
    assert ms.expired() == []
    clock[0] = 6.0
    assert ms.expired() == ["w0"]
    assert ms.remaining("w1") > 0 > ms.remaining("w0")
    ms.drop("w0")
    assert ms.workers() == ["w1"]
    clock[0] = 20.0
    assert ms.expired() == ["w1"]


@pytest.mark.slow  # round-15 tier-1 budget: the elastic kill/join
# drills (fast tier) exercise the same epoch machinery end to end.
def test_sharded_engine_epoch_remap_bit_identical(tmp_path,
                                                  monkeypatch):
    """The fast in-process epoch sibling: a single-process sharded run
    crashes mid-run, ownership is remapped by a permutation at the
    rest point (epoch bump), and restart_from completes under the new
    assignment with bit-identical totals — the epoch-keyed wave cache
    and the assignment-aware device routing both exercised without any
    multi-process arm."""
    monkeypatch.setenv("STpu_FAULTS", "wave_crash@n=3")
    reset_fault_plans()
    ckpt = str(tmp_path / "s.npz")
    c = TwoPhaseSys(RMS).checker().spawn_tpu_bfs(
        batch_size=32, sharded=True, fused=False,
        checkpoint_path=ckpt, checkpoint_every_waves=1)
    with pytest.raises(RuntimeError):
        c.join()
    reset_fault_plans()
    n = c._n_shards
    assert c.owner_epoch == 0
    with pytest.raises(RuntimeError, match="rest point"):
        # Guard probed while stopped is fine; simulate running state.
        c._done.clear()
        c.set_owner_assignment([(i + 1) % n for i in range(n)])
    c._done.set()
    c.set_owner_assignment([(i + 1) % n for i in range(n)])
    assert c.owner_epoch == 1
    c.restart_from(ckpt).join()
    assert (c.state_count(), c.unique_state_count()) == (WANT_STATES,
                                                         WANT_UNIQUE)
    assert sorted(c.discoveries()) == ["abort agreement",
                                      "commit agreement"]


# -- Per-shard checkpoint format (v4) -------------------------------------

def _shard_payload(p, of, round_=7, epoch=2):
    header = make_header(
        model_name="M", state_width=3, state_count=4, unique_count=4,
        use_symmetry=False, discoveries={},
        shard={"index": p, "of": of, "round": round_, "epoch": epoch})
    return dict(header=header,
                visited=np.arange(4, dtype=np.uint64),
                pending_vecs=np.zeros((2, 3), np.uint32),
                pending_fps=np.arange(2, dtype=np.uint64),
                pending_ebits=np.zeros(2, np.uint32))


def test_shard_checkpoint_roundtrip(tmp_path):
    path = str(tmp_path / "run.npz")
    write_atomic(shard_path(path, 3), _shard_payload(3, 8))
    header = verify_file(shard_path(path, 3))
    assert header["version"] == CKPT_VERSION
    assert header["shard"] == {"index": 3, "of": 8, "round": 7,
                               "epoch": 2}
    with load_checkpoint(shard_path(path, 3)) as data:
        validate_header(data, model_name="M", state_width=3,
                        use_symmetry=False, expect_shard=(3, 8))
        with pytest.raises(ValueError, match="wrong shard"):
            validate_header(data, model_name="M", state_width=3,
                            use_symmetry=False, expect_shard=(5, 8))


def test_v3_single_shard_file_still_loads(tmp_path):
    """A pre-v4 header (no shard section) is accepted as an adopted
    partition — expect_shard only pins headers that DECLARE a shard."""
    path = str(tmp_path / "v3.npz")
    header = json.loads(bytes(make_header(
        model_name="M", state_width=3, state_count=1, unique_count=1,
        use_symmetry=False, discoveries={}).tobytes()).decode())
    header["version"] = 3
    del header["row_format"]  # a genuinely old writer
    data = {
        "header": np.frombuffer(json.dumps(header).encode(), np.uint8),
        "visited": np.arange(2, dtype=np.uint64)}
    write_atomic(path, data)
    with load_checkpoint(path) as loaded:
        out = validate_header(loaded, model_name="M", state_width=3,
                              use_symmetry=False, expect_shard=(0, 8))
    assert out["version"] == 3 and "shard" not in out


def test_newer_checkpoint_version_refused(tmp_path):
    path = str(tmp_path / "future.npz")
    header = json.loads(bytes(make_header(
        model_name="M", state_width=3, state_count=1, unique_count=1,
        use_symmetry=False, discoveries={}).tobytes()).decode())
    header["version"] = CKPT_VERSION + 1
    write_atomic(path, {
        "header": np.frombuffer(json.dumps(header).encode(), np.uint8),
        "visited": np.arange(2, dtype=np.uint64)})
    with pytest.raises(ValueError, match="newer than this build"):
        verify_file(path)


# -- Lint: the membership invariant ---------------------------------------

def test_lint_membership_invariant():
    import trace_lint

    def evt(etype, **kw):
        base = {"type": etype, "schema_version": 4, "engine": "elastic",
                "run": "r", "t": 1.0}
        base.update(kw)
        return json.dumps(base)

    lost = evt("worker_lost", worker="w1", epoch=0)
    migrated = evt("migrate_done", partitions=4, to="w0", epoch=1)
    rebalance = evt("rebalance", partitions=2, to="w2", epoch=2)
    abort = evt("abort", reason="gave up", attempts=1)
    fault = evt("fault", point="worker_crash", hit=1, mode="raise")
    retry = evt("retry", attempt=1, backoff_s=0.1, jitter_s=0.01,
                resumed_from=None)

    _, errors = trace_lint.lint_lines([lost])
    assert errors and "never followed by a migrate_done" in errors[0]
    _, errors = trace_lint.lint_lines([lost, migrated, rebalance])
    assert not errors
    _, errors = trace_lint.lint_lines([lost, lost, abort])
    assert not errors, "terminal abort retires every outstanding loss"
    _, errors = trace_lint.lint_lines([lost, lost, migrated])
    assert len(errors) == 1, "one migrate_done retires one loss"
    # Schema v4: a supervisor retry retires a fault like a recover.
    _, errors = trace_lint.lint_lines([fault, retry])
    assert not errors


# -- Supervisor jitter (satellite) ----------------------------------------

def test_supervisor_backoff_jitter_recorded_and_seeded():
    import random

    from stateright_tpu.resilience import Supervisor

    boom = {"n": 0}

    def factory(resume_from=None):
        class C:
            def join(self):
                boom["n"] += 1
                if boom["n"] < 3:
                    raise RuntimeError("boom")
                return self
        return C()

    slept = []
    sup = Supervisor(factory, backoff_s=0.1, backoff_factor=2.0,
                     jitter_frac=0.5, rng=random.Random(7),
                     sleep=slept.append)
    sup.run()
    assert len(sup.recoveries) == 2
    for rec, base in zip(sup.recoveries, (0.1, 0.2)):
        assert rec["backoff_s"] == base
        assert 0.0 <= rec["jitter_s"] <= 0.5 * base
    for got, rec in zip(slept, sup.recoveries):
        # records round to 4 decimals; the sleep gets the exact draw
        assert got == pytest.approx(rec["backoff_s"] + rec["jitter_s"],
                                    abs=1e-4)
    # Seeded: the same rng draws the same jitter (replayable records).
    boom["n"] = 0
    slept2 = []
    sup2 = Supervisor(factory, backoff_s=0.1, backoff_factor=2.0,
                      jitter_frac=0.5, rng=random.Random(7),
                      sleep=slept2.append)
    sup2.run()
    assert slept2 == slept
    # jitter_frac=0 restores the exact pre-v4 schedule.
    boom["n"] = 0
    slept3 = []
    Supervisor(factory, backoff_s=0.1, backoff_factor=2.0,
               jitter_frac=0.0, sleep=slept3.append).run()
    assert slept3 == [0.1, 0.2]


# -- Multi-process arms (slow) --------------------------------------------

@pytest.mark.slow
def test_elastic_process_transport_kill_2pc(tmp_path):
    """The real thing: one OS process per worker (spawn context, own
    JAX CPU backend each), a real SIGKILL mid-run, migration, and
    bit-identical totals."""
    ckpt = str(tmp_path / "proc.npz")
    c = ElasticChecker(
        partial(TwoPhaseSys, RMS), workers=2, n_partitions=8,
        batch_rows=64, transport="process", checkpoint_path=ckpt,
        checkpoint_every_rounds=2, kill_at={4: "w0"}).join()
    assert (c.state_count(), c.unique_state_count()) == (WANT_STATES,
                                                         WANT_UNIQUE)
    assert [e["type"] for e in c.events] == ["worker_lost",
                                             "migrate_done"]
    assert c.workers() == ["w1"]


@pytest.mark.slow
def test_elastic_paxos_kill_and_join_exact_space(tmp_path):
    """The north-star workload through the elastic path: paxos(2,3)
    with BOTH a mid-run worker loss and a mid-run join completes to
    the exact full space (16,668 unique / 32,971 states) with the
    expected lifecycle — the elastic sibling of the round-10 paxos
    crash matrix."""
    from paxos import PaxosModelCfg

    def factory():
        return PaxosModelCfg(2, 3).into_model()

    ckpt = str(tmp_path / "paxos.npz")
    c = ElasticChecker(
        factory, workers=2, n_partitions=8, batch_rows=512,
        transport="thread", checkpoint_path=ckpt,
        checkpoint_every_rounds=4,
        kill_at={6: "w1"}, join_at={10: "w2"}).join()
    assert c.unique_state_count() == 16668
    assert c.state_count() == 32971
    assert sorted(c.discoveries()) == ["value chosen"]
    kinds = [e["type"] for e in c.events]
    assert kinds == ["worker_lost", "migrate_done", "worker_join",
                     "rebalance"]


def test_process_transport_is_refused_on_a_tpu(monkeypatch):
    """A chip belongs to one process: spawn-transport workers would
    contend with the coordinator for it, so a TPU backend refuses
    them before any worker starts."""
    from stateright_tpu.resilience import elastic

    monkeypatch.setattr(elastic, "_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="refused on a TPU"):
        ElasticChecker(partial(TwoPhaseSys, RMS), workers=2,
                       transport="process")
