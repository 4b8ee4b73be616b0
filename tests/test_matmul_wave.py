"""Matmul-form frontier expansion differential suite (ISSUE 15).

The transition-structure compiler (``tpu/matmul_wave.py``) lowers a
*regular* model's successor generation to one dense product per key
group; everything it knows comes from probing the model's own jitted
``step``, so the only correctness claim that matters is bit-identity
with the vmapped path — pinned here three ways: (1) seeded random
in-domain frontiers through ``matmul_expand`` vs ``expand_frontier``
for every regular corpus model, (2) the knob-on/off engine matrix
(counts, discoveries, parent maps, checkpoint payload bytes) on all
four device engines including the megakernel composition, and (3) the
capability gate — every corpus model classifies deterministically with
a stable reason string, and an irregular model with the knob on warns
once and runs the step path with identical results.

Tier-1 budget: the fused/classic engine pair is the fast gate; the
sharded pair (shard_map interpret compiles) rides ``-m slow``.
"""

import os
import sys
import warnings

import numpy as np
import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "examples"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from two_phase_commit import TwoPhaseSys  # noqa: E402

from stateright_tpu.tpu import engine as eng  # noqa: E402
from stateright_tpu.tpu.engine import expand_frontier  # noqa: E402
from stateright_tpu.tpu.matmul_wave import (  # noqa: E402
    KEY_DOMAIN_CAP, LANE_DOMAIN_CAP, classify, matmul_expand, plan_bytes)
from stateright_tpu.tpu.packing import compile_layout  # noqa: E402


def _spawn(model, engine, B, **kwargs):
    b = model.checker()
    if engine == "fused":
        return b.spawn_tpu_bfs(batch_size=B, fused=True, **kwargs)
    if engine == "classic":
        return b.spawn_tpu_bfs(batch_size=B, fused=False, **kwargs)
    if engine == "sharded-fused":
        return b.spawn_tpu_bfs(batch_size=B, sharded=True, **kwargs)
    assert engine == "sharded-classic"
    return b.spawn_tpu_bfs(batch_size=B, sharded=True, fused=False,
                           **kwargs)


def _ckpt_payload(path):
    """Every npz member's raw bytes (member-wise, not whole-file: the
    zip container embeds timestamps; the PAYLOAD is what must match)."""
    with np.load(path) as data:
        return {k: data[k].tobytes() for k in sorted(data.files)}


def _random_frontier(rng, dm, n):
    """``n`` uniform in-domain state rows (uint32 [n, W]) straight from
    the model's declared lane domains — the fuzz inputs are *arbitrary*
    in-domain vectors, not only reachable states, so the tables must be
    right everywhere the contract says they are."""
    layout = compile_layout(dm.lane_bits(), dm.state_width)
    cols = [rng.integers(0, 1 << lane.bits, size=n, dtype=np.uint32)
            for lane in layout.lanes]
    return np.stack(cols, axis=1)


# -- The compiler: corpus classification pins ------------------------------

#: Every corpus model's verdict at the registry defaults — the gate is
#: part of the public surface (scheduler_stats()["wave_matmul"]
#: .reason), so these strings are pinned, not pattern-matched. A model
#: change that flips one is a contract change and must edit this table.
CORPUS_REASONS = {
    "abd": "sentinel lane domains",
    "increment": "regular (6 key groups, 816 macs/row)",
    "increment_lock": "regular (9 key groups, 1776 macs/row)",
    "paxos": "sentinel lane domains",
    "pingpong": "undeclared lane_bits",
    "single_copy": "sentinel lane domains",
    "sliding_puzzle": "undeclared lane_bits",
    "twopc": "regular (8 key groups, 1640 macs/row)",
    "vsr": "undeclared lane_bits",
}


def test_corpus_classification_is_pinned():
    from stateright_tpu.service import default_registry

    r = default_registry()
    assert set(CORPUS_REASONS) == set(r.names())
    for name in r.names():
        model, _ = r.build(name)
        cls = classify(model.device_model())
        assert cls.reason == CORPUS_REASONS[name], name
        assert cls.regular == cls.reason.startswith("regular"), name
        assert (cls.plan is not None) == cls.regular, name


def test_classification_is_memoized_by_native_form():
    """Probing costs thousands of step evaluations; engines classify at
    spawn time, so same canonical model form -> the same plan object."""
    a = classify(TwoPhaseSys(3).device_model())
    b = classify(TwoPhaseSys(3).device_model())
    assert a is b
    assert a.plan is not None


def test_plan_shape_and_bytes_accounting():
    """The VMEM term the megakernel gate budgets: the widest one-hot
    block at the batch plus every resident table, and 0 for no plan."""
    plan = classify(TwoPhaseSys(3).device_model()).plan
    assert plan.matmul_ops == sum(g.domain * g.table.shape[1]
                                  for g in plan.groups)
    assert plan.table_bytes == sum(g.table.nbytes for g in plan.groups)
    for g in plan.groups:
        assert g.domain <= KEY_DOMAIN_CAP
        assert all((1 << 0) <= g.domain <= LANE_DOMAIN_CAP ** len(g.keys)
                   for _ in g.keys)
        # Tabulated entries are exact f32 integers below 2^16 — the
        # invariant the uint32 reconstruction leans on.
        assert float(np.abs(plan.groups[0].table).max()) < (1 << 16)
    widest = max(g.domain for g in plan.groups)
    assert plan_bytes(plan, 64) == 4 * 64 * widest + plan.table_bytes
    assert plan_bytes(None, 64) == 0


# -- The compiler: differential fuzz ---------------------------------------

@pytest.mark.parametrize("make", [
    pytest.param(lambda: TwoPhaseSys(3), id="twopc3"),
    pytest.param(lambda: TwoPhaseSys(4), id="twopc4"),
    pytest.param(lambda: __import__("increment").IncrementModel(2),
                 id="increment2"),
    pytest.param(
        lambda: __import__("increment_lock").IncrementLockModel(2),
        id="increment_lock2"),
])
def test_matmul_expand_matches_step_on_random_frontiers(make):
    """Seeded random in-domain frontiers: every return of
    ``matmul_expand`` — successor rows, validity, count, terminal mask
    — bit-identical to the vmapped ``step`` path."""
    model = make()
    dm = model.device_model()
    cls = classify(dm)
    assert cls.regular, cls.reason
    B = 64
    j_ref = jax.jit(lambda v, m: expand_frontier(dm, v, m))
    j_mm = jax.jit(lambda v, m: matmul_expand(dm, cls.plan, v, m))
    for seed in range(5):
        rng = np.random.default_rng(seed)
        vecs = jnp.asarray(_random_frontier(rng, dm, B))
        valid = jnp.asarray(rng.random(B) < 0.9)
        ref, mm = j_ref(vecs, valid), j_mm(vecs, valid)
        for i, (a, b) in enumerate(zip(ref, mm)):
            a, b = np.asarray(a), np.asarray(b)
            if i == 0:  # successor rows: garbage where invalid
                keep = np.asarray(ref[1])
                assert np.array_equal(a[keep], b[keep]), (seed, i)
            else:
                assert np.array_equal(a, b), (seed, i)


@pytest.mark.slow
@pytest.mark.parametrize("make", [
    pytest.param(lambda: TwoPhaseSys(5), id="twopc5"),
    pytest.param(lambda: __import__("increment").IncrementModel(3),
                 id="increment3"),
    pytest.param(
        lambda: __import__("increment_lock").IncrementLockModel(3),
        id="increment_lock3"),
])
def test_matmul_expand_fuzz_wide(make):
    """The slow-tier arm: bigger configs, 30 seeds."""
    model = make()
    dm = model.device_model()
    cls = classify(dm)
    assert cls.regular, cls.reason
    B = 128
    j_ref = jax.jit(lambda v, m: expand_frontier(dm, v, m))
    j_mm = jax.jit(lambda v, m: matmul_expand(dm, cls.plan, v, m))
    for seed in range(30):
        rng = np.random.default_rng(seed)
        vecs = jnp.asarray(_random_frontier(rng, dm, B))
        valid = jnp.asarray(rng.random(B) < 0.9)
        ref, mm = j_ref(vecs, valid), j_mm(vecs, valid)
        keep = np.asarray(ref[1])
        assert np.array_equal(np.asarray(ref[0])[keep],
                              np.asarray(mm[0])[keep]), seed
        for a, b in zip(ref[1:], mm[1:]):
            assert np.array_equal(np.asarray(a), np.asarray(b)), seed


# -- Engine-level parity matrix --------------------------------------------

@pytest.mark.parametrize("engine", [
    "fused", "classic",
    pytest.param("sharded-fused", marks=pytest.mark.slow),
    pytest.param("sharded-classic", marks=pytest.mark.slow)])
def test_wave_matmul_bit_identical_2pc(engine, tmp_path):
    """ISSUE 15 acceptance: wave_matmul on vs off — counts,
    discoveries, parent maps, and checkpoint payload bytes bit-identical
    on all four engines; attribution records the executed path."""
    model = TwoPhaseSys(3)
    runs = {}
    for on in (True, False):
        path = str(tmp_path / f"{engine}-{on}.npz")
        c = _spawn(model, engine, 48, checkpoint_path=path,
                   wave_matmul=on).join()
        runs[on] = (c.unique_state_count(), c.state_count(),
                    set(c.discoveries()), dict(c._parent_map()),
                    _ckpt_payload(path))
        wm = c.scheduler_stats()["wave_matmul"]
        assert wm["enabled"] is on
        assert wm["active"] is on
        assert wm["expand_impl"] == ("matmul" if on else "step")
        if on:
            assert wm["reason"] == CORPUS_REASONS["twopc"]
            assert wm["matmul_ops"] == 1640
            assert c.kernel_path().endswith("+matmul")
            assert all(e["expand_impl"] == "matmul"
                       for e in c.dispatch_log)
        else:
            assert not c.kernel_path().endswith("+matmul")
    assert runs[True][:4] == runs[False][:4], engine
    assert runs[True][4] == runs[False][4], \
        f"{engine}: checkpoint payload bytes differ with wave_matmul on"


def test_wave_matmul_composes_with_megakernel(tmp_path):
    """Both knobs on: the matmul expand runs INSIDE the single-kernel
    wave (tables ride as pallas operands) and attribution carries both
    axes — still bit-identical to both knobs off."""
    from stateright_tpu.tpu.pallas_table import PALLAS_AVAILABLE

    if not PALLAS_AVAILABLE:
        pytest.skip("pallas not available in this jax build")
    model = TwoPhaseSys(3)
    runs = {}
    for on in (True, False):
        path = str(tmp_path / f"mega-{on}.npz")
        c = _spawn(model, "classic", 48, checkpoint_path=path,
                   wave_kernel=on, wave_matmul=on).join()
        runs[on] = (c.unique_state_count(), c.state_count(),
                    set(c.discoveries()), dict(c._parent_map()),
                    _ckpt_payload(path))
        if on:
            assert c.kernel_path() == "interpret+matmul"
    assert runs[True] == runs[False]


@pytest.mark.slow
def test_wave_matmul_bit_identical_2pc5_fused():
    """A deeper regular workload (2pc @ 5 RMs) through the fused
    engine, knob on vs off (slow tier)."""
    model = TwoPhaseSys(5)
    runs = {}
    for on in (True, False):
        c = _spawn(model, "fused", 256, wave_matmul=on).join()
        runs[on] = (c.unique_state_count(), c.state_count(),
                    set(c.discoveries()), dict(c._parent_map()))
    assert runs[True] == runs[False]


# -- The capability gate ---------------------------------------------------

def test_irregular_model_gates_to_fallback():
    """Paxos with the knob on: one RuntimeWarning naming the reason,
    then the vmapped step path with counts identical to knob-off — a
    tenant flipping the knob on an irregular model must never see a
    different answer (or a crash)."""
    from paxos import PaxosModelCfg

    model = PaxosModelCfg(1, 3).into_model()
    eng._WAVE_MATMUL_GATE_WARNED.discard("PaxosDevice")
    with pytest.warns(RuntimeWarning, match="not matmul-regular "
                                            r"\(sentinel lane domains\)"):
        on = _spawn(model, "classic", 64, wave_matmul=True).join()
    wm = on.scheduler_stats()["wave_matmul"]
    assert wm == {"enabled": True, "active": False,
                  "expand_impl": "step",
                  "reason": "sentinel lane domains", "matmul_ops": 0}
    assert not on.kernel_path().endswith("+matmul")
    # Once per model type, not per spawn.
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        again = _spawn(model, "classic", 64, wave_matmul=True).join()
    off = _spawn(model, "classic", 64, wave_matmul=False).join()
    assert on.unique_state_count() == off.unique_state_count() \
        == again.unique_state_count()
    assert on.state_count() == off.state_count()
    assert set(on.discoveries()) == set(off.discoveries())


def test_ad_hoc_model_without_lane_bits_is_irregular():
    class Anon:
        state_width, max_fanout = 2, 2

    cls = classify(Anon())
    assert (cls.regular, cls.plan) == (False, None)
    assert cls.reason == "undeclared lane_bits"


def test_env_knob_resolution(monkeypatch):
    """wave_matmul=None follows STpu_WAVE_MATMUL; explicit kwargs win.
    The resolved activation is part of the shared program-cache key."""
    model = TwoPhaseSys(2)
    monkeypatch.setenv("STpu_WAVE_MATMUL", "1")
    c = model.checker().spawn_tpu_bfs(batch_size=16, fused=False).join()
    assert c._wave_matmul_on is True
    assert c._matmul_plan is not None
    monkeypatch.setenv("STpu_WAVE_MATMUL", "0")
    c = model.checker().spawn_tpu_bfs(batch_size=16, fused=False).join()
    assert c._wave_matmul_on is False
    assert c._matmul_plan is None
    monkeypatch.setenv("STpu_WAVE_MATMUL", "1")
    c = model.checker().spawn_tpu_bfs(batch_size=16, fused=False,
                                      wave_matmul=False).join()
    assert c._wave_matmul_on is False


# -- Observability and service surface -------------------------------------

def test_wave_events_carry_expand_impl(tmp_path):
    """Wave events gain the v12 key: expand_impl names the executed
    expansion; the traced stream schema-validates line by line and the
    matmul_ops gauge lands once at run start."""
    import json

    from stateright_tpu.obs.schema import validate_line

    trace = str(tmp_path / "trace.jsonl")
    model = TwoPhaseSys(3)
    c = _spawn(model, "fused", 48, wave_matmul=True,
               trace_path=trace).join()
    waves, gauges = 0, []
    with open(trace) as f:
        for line in f:
            assert validate_line(line) == [], line
            evt = json.loads(line)
            if evt.get("type") == "wave":
                waves += 1
                assert evt["expand_impl"] == "matmul"
                assert evt["kernel_path"].endswith("+matmul")
            if evt.get("type") == "gauge" and \
                    evt.get("name") == "matmul_ops":
                gauges.append(evt["value"])
    assert waves == len(c.dispatch_log)
    assert gauges == [1640.0]


def test_schema_v11_field_map_excludes_v12_keys():
    """A v11 wave with the v12 rider is NOT valid, and a v12 wave
    missing it is NOT valid — additions go through the version bump,
    one schema per version."""
    from stateright_tpu.obs.schema import (WAVE_FIELDS, WAVE_FIELDS_V11,
                                           validate_event)

    assert "expand_impl" not in WAVE_FIELDS_V11
    assert "expand_impl" in WAVE_FIELDS
    base = {"type": "wave", "schema_version": 11, "engine": "classic",
            "run": "x", "wave": 0, "t": 1.0}
    for k in WAVE_FIELDS_V11:
        base.setdefault(k, None)
    base.update(states=1, unique=1, bucket=4, waves=1, inflight=0,
                compiled=False, successors=0, candidates=0, novel=0,
                overflow=False)
    assert validate_event(base) == []
    bad = dict(base, expand_impl="matmul")
    assert any("unexpected" in e for e in validate_event(bad))
    v12 = dict(base, schema_version=12)
    assert any("missing field 'expand_impl'" in e
               for e in validate_event(v12))
    assert validate_event(dict(v12, expand_impl=None)) == []


def test_service_allowlists_wave_matmul_knob():
    """Tenants may A/B the knob through the job API; unknown knobs
    still 400."""
    from stateright_tpu.service.jobs import _KNOBS

    assert _KNOBS.get("wave_matmul") is bool
