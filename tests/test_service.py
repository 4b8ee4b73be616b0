"""Checking-as-a-service (round 14): the corpus registry, the
differential fuzz gate, and the multi-tenant job service end to end
over real HTTP — including the acceptance gate: two concurrent jobs
sharing a cached wave program, a preemption resumed to bit-identical
final counters, per-job traces that lint clean, and the ``stpu_job_*``
metric families.

The fast tier keeps every job tiny (2pc @ 3 RMs — 288 states); the
fused-engine arm and the corpus-wide walk sweep run behind ``-m slow``.
"""

import json
import os
import sys
import time

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_REPO, "tools"))

import service_client as sc  # noqa: E402
import trace_lint  # noqa: E402
import trace_summary  # noqa: E402

from stateright_tpu.obs.schema import validate_line  # noqa: E402
from stateright_tpu.service import (DiffMismatch, JobError,  # noqa: E402
                                    JobService, default_registry,
                                    diff_walk, fuzz_gate)

TWOPC = {"model": "twopc", "params": {"rm_count": 3},
         "knobs": {"batch_size": 64}}


# -- Registry --------------------------------------------------------------


def test_registry_corpus():
    r = default_registry()
    names = r.names()
    # The 8 existing models + the round-14 VR addition.
    assert names == ["abd", "increment", "increment_lock", "paxos",
                     "pingpong", "single_copy", "sliding_puzzle",
                     "twopc", "vsr"]
    with pytest.raises(KeyError):
        r.entry("raft")
    with pytest.raises(ValueError):
        r.resolve_params("twopc", {"rms": 5})  # unknown key
    # Coercion: JSON submissions arrive stringly/floaty.
    assert r.resolve_params("twopc", {"rm_count": "5"}) == {"rm_count": 5}
    # Canonical program keys: same params (any spelling) — same key.
    assert r.program_key("twopc", {"rm_count": 3}) == \
        r.program_key("twopc", None)
    assert r.program_key("twopc", {"rm_count": 5}) != \
        r.program_key("twopc", None)
    listing = r.describe()
    assert any(e["name"] == "vsr" and e["params"]["n"] == 3
               for e in listing)


def test_submit_validation():
    svc = JobService(workers=1)
    try:
        with pytest.raises(JobError):
            svc.submit({"model": "raft"})
        with pytest.raises(JobError):
            svc.submit({"model": "twopc", "engine": "warp"})
        with pytest.raises(JobError):
            svc.submit({"model": "twopc", "knobs": {"donate": True}})
        with pytest.raises(JobError):
            svc.submit({"model": "twopc", "properties": ["nope"]})
        with pytest.raises(JobError):
            svc.submit({"model": "twopc", "params": {"rm_count": "x"}})
    finally:
        svc.close()


@pytest.mark.parametrize("knob", ["table_impl", "wave_kernel",
                                  "wave_matmul"])
def test_removed_knob_is_refused_with_400(knob, tmp_path):
    """Knobs the engines no longer take are unknown knobs: the HTTP API
    answers 400, as for any other."""
    from stateright_tpu.explorer import serve_service

    service, server = serve_service(
        addresses=("127.0.0.1", 0), block=False, workers=1,
        data_dir=str(tmp_path))
    host, port = server.server_address[:2]
    try:
        with pytest.raises(sc.ServiceError) as err:
            sc.submit(f"http://{host}:{port}",
                      dict(TWOPC, knobs={knob: "xla"}))
        assert err.value.http_status == 400
        assert f"unknown engine knob {knob!r}" in str(err.value)
    finally:
        server.shutdown()
        server.server_close()
        service.close()


# -- Differential fuzz gate ------------------------------------------------


def test_diff_walk_catches_broken_device_model():
    """The gate's reason to exist: a device form with a deliberately
    wrong transition must not pass."""
    import stateright_tpu.actor.actor_test_util as ppmod
    from stateright_tpu.actor.actor_test_util import PingPongCfg
    from stateright_tpu.tpu.models.pingpong import PingPongDevice

    class BrokenPingPong(PingPongDevice):
        def deliver(self, body, env):
            import jax.numpy as jnp

            new_body, handled, outs = super().deliver(body, env)
            # Deliberate bug: drop every delivery's validity — the
            # device silently loses all message-driven successors.
            return new_body, handled & jnp.zeros((), bool), outs

    cfg = PingPongCfg(maintains_history=False, max_nat=2)
    model = cfg.into_model()
    with pytest.raises(DiffMismatch, match="successor sets disagree"):
        diff_walk(model, BrokenPingPong(cfg, ppmod), seed=0, steps=10)


def test_diff_walk_catches_broken_property():
    import stateright_tpu.actor.actor_test_util as ppmod
    from stateright_tpu.actor.actor_test_util import PingPongCfg
    from stateright_tpu.tpu.models.pingpong import PingPongDevice

    class WrongProperty(PingPongDevice):
        def device_properties(self):
            import jax.numpy as jnp

            props = super().device_properties()
            props["can reach max"] = lambda v: jnp.ones((), bool)
            return props

    cfg = PingPongCfg(maintains_history=False, max_nat=2)
    model = cfg.into_model()
    with pytest.raises(DiffMismatch, match="property"):
        diff_walk(model, WrongProperty(cfg, ppmod), seed=0, steps=10)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("name", default_registry().names())
def test_fuzz_gate_walks(name, seed):
    """Every corpus model's device step agrees with its host model on a
    seeded random-schedule walk."""
    result = fuzz_gate(name, seeds=(seed,), steps=15, full=False)
    assert result["walks"][0]["transitions"] > 0


# -- The service end to end (acceptance gate) ------------------------------


def _wait(base, job_id, timeout=120.0):
    return sc.wait_for(base, job_id, timeout=timeout, poll_s=0.1)


def test_service_end_to_end_http(tmp_path):
    from stateright_tpu.explorer import serve_service

    service, server = serve_service(
        addresses=("127.0.0.1", 0), block=False, workers=2,
        data_dir=str(tmp_path))
    host, port = server.server_address[:2]
    base = f"http://{host}:{port}"
    try:
        # Corpus listing over HTTP.
        assert any(e["name"] == "vsr" for e in sc.corpus(base))

        # Two CONCURRENT same-model jobs: submitted back to back into a
        # 2-worker pool, so they race — the per-key build lock means
        # one pays the XLA compile and the other HITS the shared cache.
        j1 = sc.submit(base, TWOPC)
        j2 = sc.submit(base, TWOPC)
        s1, s2 = _wait(base, j1["id"]), _wait(base, j2["id"])
        assert s1["state"] == s2["state"] == "done"
        assert s1["unique"] == s2["unique"] == 288
        assert s1["states"] == s2["states"] == 1146
        assert s1["jit_cache"]["shared"] and s2["jit_cache"]["shared"]
        assert s1["jit_cache"]["hits"] + s2["jit_cache"]["hits"] > 0
        # Verdicts ride the status payload, explorer-style.
        names = {name for _, name, _ in s1["properties"]}
        assert "consistent" in names

        # Preempt over HTTP -> resumable checkpoint -> resubmission
        # finishes with BIT-IDENTICAL final counters.
        j3 = sc.submit(base, {"model": "twopc",
                              "knobs": {"batch_size": 8,
                                        "checkpoint_every_waves": 1}})
        while sc.status(base, j3["id"])["state"] == "queued":
            time.sleep(0.02)
        sc.preempt(base, j3["id"])
        s3 = _wait(base, j3["id"])
        # (A very fast box may finish before the preempt lands — then
        # the run is simply done and there is nothing to resume.)
        if s3["state"] == "preempted":
            assert s3["checkpoint"]
            j4 = sc.resume(base, j3["id"])
            # Second resume of the same job: 409 — two supervisors on
            # one checkpoint rotation would corrupt the generation.
            with pytest.raises(sc.ServiceError) as err:
                sc.resume(base, j3["id"])
            assert err.value.http_status == 409
            s4 = _wait(base, j4["id"])
            assert s4["state"] == "done"
            assert s4["resume_of"] == j3["id"]
            assert (s4["states"], s4["unique"]) == (1146, 288)

        # Per-job traces lint clean, job lifecycle pairing included.
        for payload in sc.jobs(base):
            counts, errors = trace_lint.lint_file(
                service.trace_file(payload["id"]))
            assert not errors, errors[:3]
            assert counts.get("job_submit") == 1
        # Every line of a job trace is schema-valid v7.
        for line in sc.trace_lines(base, j1["id"]):
            assert not validate_line(line)

        # The trace_summary per-job table.
        events = trace_summary.load_events(
            service.trace_file(j1["id"]))
        jobs_tbl = trace_summary.summarize_jobs(events)
        assert jobs_tbl[j1["id"]]["outcome"] == "done"
        assert jobs_tbl[j1["id"]]["states"] == 1146
        assert j1["id"] in trace_summary.format_job_table(jobs_tbl)

        # stpu_job_* metric families on /.metrics.
        metrics = sc.request(base, "/.metrics")
        assert 'stpu_jobs{state="done"}' in metrics
        assert "stpu_job_program_cache_hits_total" in metrics
        assert (f'stpu_job_states_total{{job="{j1["id"]}"}} 1146'
                in metrics)
        # Round-19: the deprecated bare counter duals are gone.
        assert f'stpu_job_states{{job="{j1["id"]}"}}' not in metrics

        # Error mapping: 400 bad spec, 404 unknown id, 409 conflict.
        for bad, code in ((lambda: sc.submit(base, {"model": "nope"}),
                           400),
                          (lambda: sc.status(base, "j-9999"), 404),
                          (lambda: sc.resume(base, j1["id"]), 409)):
            with pytest.raises(sc.ServiceError) as err:
                bad()
            assert err.value.http_status == code

        # The CLI entry points answer against a live service.
        assert sc.main(["--url", base, "corpus"]) == 0
        assert sc.main(["--url", base, "status", j1["id"]]) == 0
        assert sc.main(["--url", base, "trace", j1["id"],
                        "--tail", "3"]) == 0
    finally:
        server.shutdown()
        server.server_close()
        service.close()


def test_job_trace_lint_pairing_unit(tmp_path):
    """The v7 stream invariant, schema-level: an unpaired job_submit
    fails the lint; done/abort pair by exact job id."""
    def line(etype, job, **extra):
        evt = {"type": etype, "schema_version": 7, "engine": "service",
               "run": "r0", "t": 1.0, "job": job}
        evt.update(extra)
        return json.dumps(evt)

    good = [line("job_submit", "j-1", model="twopc",
                 job_engine="classic"),
            line("job_submit", "j-2", model="vsr",
                 job_engine="fused"),
            line("job_abort", "j-2", reason="preempted"),
            line("job_done", "j-1", states=10, unique=5)]
    counts, errors = trace_lint.lint_lines(good)
    assert not errors and counts["job_submit"] == 2

    lost = good[:2]  # two submits, nothing resolved
    _, errors = trace_lint.lint_lines(lost)
    assert len(errors) == 2
    assert all("job_submit" in e for e in errors)

    # Exact-key pairing: j-2's abort cannot retire j-1's submit.
    crossed = [good[0], line("job_abort", "j-2", reason="failed: x")]
    _, errors = trace_lint.lint_lines(crossed)
    assert len(errors) == 1 and "'j-1'" in errors[0]


@pytest.mark.slow
def test_service_fused_jobs_and_host_engine(tmp_path):
    """Fused-engine jobs share dispatch programs too; host-engine jobs
    run (and refuse preemption while running)."""
    svc = JobService(workers=2, data_dir=str(tmp_path))
    try:
        f1 = svc.submit(dict(TWOPC, engine="fused"))
        f2 = svc.submit(dict(TWOPC, engine="fused"))
        h1 = svc.submit({"model": "pingpong", "engine": "host",
                         "params": {"max_nat": 2}})
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            states = [svc.status(j["id"])["state"]
                      for j in (f1, f2, h1)]
            if all(s not in ("queued", "running") for s in states):
                break
            time.sleep(0.1)
        sf1, sf2 = svc.status(f1["id"]), svc.status(f2["id"])
        assert sf1["state"] == sf2["state"] == "done"
        assert sf1["unique"] == sf2["unique"] == 288
        assert sf1["jit_cache"]["hits"] + sf2["jit_cache"]["hits"] > 0
        sh = svc.status(h1["id"])
        assert sh["state"] == "done" and sh["jit_cache"] is None
    finally:
        svc.close()


@pytest.mark.slow
def test_fuzz_gate_corpus_walks():
    """Every corpus model passes seeded random-schedule walks — the
    cheap cross-validation gate future additions run through."""
    for name, params in [("twopc", None), ("pingpong", None),
                         ("increment", None), ("increment_lock", None),
                         ("sliding_puzzle", None),
                         ("vsr", {"n": 2})]:
        fuzz_gate(name, params=params, seeds=(0, 1), steps=15,
                  full=False)
