"""Oracle tests for the sort-free intra-wave dedup.

``first_occurrence_candidates`` (engine.py) decides which of a wave's
successors reach the table probe; since round 5 it is a scatter-min
group-resolution loop instead of a stable argsort, so pin its exact
semantics — True at the earliest frontier-order occurrence of
each non-sentinel fingerprint — against a reference oracle, including
the adversarial shapes that stress the loop (same-fp floods, shared
probe steps, all-sentinel waves).
"""
import numpy as np
import pytest

jnp = pytest.importorskip("jax.numpy")

from stateright_tpu.tpu.engine import first_occurrence_candidates  # noqa: E402
from stateright_tpu.tpu.hashing import SENTINEL  # noqa: E402


def oracle(fps):
    seen, out = set(), []
    for f in fps:
        f = int(f)
        if f == SENTINEL or f in seen:
            out.append(False)
        else:
            seen.add(f)
            out.append(True)
    return np.array(out, bool)


def check(fps):
    fps = np.asarray(fps, np.uint64)
    got = np.asarray(first_occurrence_candidates(jnp.asarray(fps)))
    want = oracle(fps)
    assert (got == want).all(), np.nonzero(got != want)[0][:5]


def test_all_identical():
    check(np.full(37, 12345, np.uint64))


def test_all_distinct():
    rng = np.random.default_rng(0)
    check(rng.integers(1, 2**63, 1000, dtype=np.uint64))


def test_triplicated_with_sentinels():
    rng = np.random.default_rng(1)
    x = rng.integers(1, 2**63, 300, dtype=np.uint64)
    check(np.concatenate([x, x, x, np.full(50, SENTINEL, np.uint64)]))


def test_all_sentinel():
    check(np.full(8, SENTINEL, np.uint64))


def test_singleton_and_tiny():
    check(np.array([SENTINEL], np.uint64))
    check(np.array([7, 7, SENTINEL, 7, 9], np.uint64))


def test_realistic_wave_shape():
    rng = np.random.default_rng(2)
    base = rng.integers(1, 2**63, 7500, dtype=np.uint64)
    wave = np.concatenate([base, rng.choice(base, 22528 - len(base))])
    rng.shuffle(wave)
    check(wave)


def test_shared_probe_steps():
    # fps differing only in high bits share low-bit-derived quantities;
    # stresses groups that keep colliding across rounds.
    rng = np.random.default_rng(3)
    check((rng.integers(1, 2**20, 5000, dtype=np.uint64) << np.uint64(44))
          | np.uint64(5))


@pytest.mark.slow  # ~11s randomized oracle fuzz; the adversarial
# deterministic streams in test_local_dedup stay the fast gate
def test_random_fuzz_vs_oracle():
    rng = np.random.default_rng(4)
    for _ in range(25):
        n = int(rng.integers(1, 400))
        pool = rng.integers(1, 50, size=max(n // 2, 1), dtype=np.uint64)
        fps = rng.choice(
            np.concatenate([pool, np.array([SENTINEL], np.uint64)]),
            size=n)
        check(fps)
