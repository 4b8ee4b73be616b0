"""Bounded background writer: hide host I/O behind device compute.

Rounds 9/15/16 optimized the device path; every host-side I/O still ran
synchronously inside the wave loop — checkpoint CRC + ``write_atomic``,
tiered-store cold-segment writes, elastic shard writes — so the device
idled while the host serialized. The ``AsyncWriter`` here is the
round-17 answer: ONE daemon thread plus a small bounded task queue (the
"double-buffered snapshot slots") that safe points hand completed work
to.

The contract that keeps the knob bit-identical to the sync path:

* **Capture is synchronous.** The caller snapshots its arrays at the
  rest point (same instant the sync path would), so the bytes handed to
  the writer are exactly what a sync write would have serialized. Only
  CRC/compress/rotate/rename move off-thread.
* **Safe points join first.** ``join()`` waits for every submitted task
  and re-raises the FIRST captured failure, clearing it — so a fault
  injected on the writer thread (``torn_ckpt``, ``spill_fail``,
  ``disk_full``) surfaces at the next safe point on the wave-loop
  thread, where the Supervisor / flight-recorder / trace-lint machinery
  already knows how to handle it. Generation ordering is free: one FIFO
  thread, and the next checkpoint joins any still-pending write before
  submitting its own, so keep-last-2 rotation order is preserved.
* **Bounded queue.** ``submit`` blocks once ``slots`` tasks are
  outstanding — the wave loop can run at most that far ahead of the
  disk, so memory held by captured snapshots stays bounded.

``SyncWriter`` is the knob-off twin: same surface, ``submit`` runs the
task inline (exceptions propagate immediately, exactly the pre-round-17
behavior), ``join`` is a no-op. Call sites stay uniform either way.
"""

from __future__ import annotations

import os
import queue
import threading
import time
from typing import Callable, Dict, Optional

#: env knob: unset/""/"0" = off, anything else on.
ASYNC_IO_ENV = "STpu_ASYNC_IO"


def async_io_from_env() -> bool:
    """The env-knob default for the ``async_io`` kwarg."""
    return os.environ.get(ASYNC_IO_ENV, "") not in ("", "0")


def resolve_async_io(knob: Optional[bool]) -> bool:
    """kwarg > env."""
    return async_io_from_env() if knob is None else bool(knob)


class SyncWriter:
    """Null-object twin of ``AsyncWriter``: runs every task inline on
    the calling thread. Keeps the same stats surface so telemetry
    consumers read one shape regardless of the knob."""

    enabled = False

    def __init__(self) -> None:
        self._stats: Dict[str, float] = {
            "submitted": 0, "completed": 0, "failed": 0,
            "joins": 0, "join_wait_s": 0.0, "busy_s": 0.0}
        self._by_kind: Dict[str, int] = {}

    def submit(self, fn: Callable[[], None], *, kind: str = "write") -> None:
        self._stats["submitted"] += 1
        self._by_kind[kind] = self._by_kind.get(kind, 0) + 1
        t0 = time.monotonic()
        try:
            fn()
        except BaseException:
            self._stats["failed"] += 1
            raise
        finally:
            self._stats["busy_s"] += time.monotonic() - t0
            self._stats["completed"] += 1

    def join(self) -> None:
        """No-op: inline tasks finished (or raised) at submit."""

    def drain(self) -> None:
        """No-op twin of the non-raising drain."""

    def reset(self) -> None:
        """No-op: nothing pending, no captured error."""

    def pending(self) -> int:
        return 0

    def close(self) -> None:
        pass

    def stats(self) -> dict:
        s = dict(self._stats)
        s.update(enabled=False, pending=0, overlap_s=0.0,
                 by_kind=dict(self._by_kind))
        s["join_wait_s"] = round(s["join_wait_s"], 6)
        s["busy_s"] = round(s["busy_s"], 6)
        return s


class AsyncWriter:
    """One writer thread + a bounded slot queue. See the module doc for
    the safe-point contract."""

    enabled = True

    def __init__(self, *, slots: int = 2,
                 name: str = "stpu-async-io") -> None:
        self._q: "queue.Queue" = queue.Queue(maxsize=max(1, int(slots)))
        self._cv = threading.Condition()
        self._outstanding = 0
        self._error: Optional[BaseException] = None
        self._closed = False
        self._stats: Dict[str, float] = {
            "submitted": 0, "completed": 0, "failed": 0,
            "joins": 0, "join_wait_s": 0.0, "busy_s": 0.0}
        self._by_kind: Dict[str, int] = {}
        self._thread = threading.Thread(
            target=self._loop, daemon=True, name=name)
        self._thread.start()

    # -- caller side -----------------------------------------------------

    def submit(self, fn: Callable[[], None], *, kind: str = "write") -> None:
        """Queues ``fn`` for the writer thread; blocks while both slots
        are full (the wave loop may run at most ``slots`` writes ahead).
        Failures do NOT surface here — they surface at the next
        ``join()``, i.e. the next safe point."""
        with self._cv:
            if self._closed:
                raise RuntimeError("submit() on a closed AsyncWriter")
            self._outstanding += 1
            self._stats["submitted"] += 1
            self._by_kind[kind] = self._by_kind.get(kind, 0) + 1
        self._q.put((fn, kind))

    def join(self) -> None:
        """Waits for every submitted task, then re-raises the first
        captured failure (clearing it). This is THE safe-point rule:
        a fault that fired on the writer thread becomes an ordinary
        wave-loop exception here, on the thread whose Supervisor /
        postmortem machinery expects it."""
        t0 = time.monotonic()
        with self._cv:
            while self._outstanding:
                self._cv.wait()
            self._stats["joins"] += 1
            self._stats["join_wait_s"] += time.monotonic() - t0
            err, self._error = self._error, None
        if err is not None:
            raise err

    def drain(self) -> Optional[BaseException]:
        """Like ``join`` but returns the captured failure instead of
        raising (shutdown paths that must not throw)."""
        with self._cv:
            while self._outstanding:
                self._cv.wait()
            err, self._error = self._error, None
        return err

    def reset(self) -> None:
        """Drops any captured failure after draining — restart_from()
        recovery: the failed generation's error was already surfaced
        (or superseded) by the resume."""
        self.drain()

    def pending(self) -> int:
        with self._cv:
            return self._outstanding

    def close(self) -> None:
        """Drains outstanding work and stops the thread. Never raises;
        a still-captured failure is dropped (close() runs on paths that
        already know the run's outcome)."""
        with self._cv:
            if self._closed:
                return
            self._closed = True
        self.drain()
        self._q.put(None)
        self._thread.join(timeout=30.0)

    def stats(self) -> dict:
        with self._cv:
            s = dict(self._stats)
            s.update(enabled=True, pending=self._outstanding,
                     by_kind=dict(self._by_kind))
        # Seconds the writer worked that the wave loop did NOT wait for:
        # the overlap the knob buys.
        s["overlap_s"] = round(max(0.0, s["busy_s"] - s["join_wait_s"]), 6)
        s["join_wait_s"] = round(s["join_wait_s"], 6)
        s["busy_s"] = round(s["busy_s"], 6)
        return s

    # -- writer thread ---------------------------------------------------

    def _loop(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            fn, _kind = item
            t0 = time.monotonic()
            try:
                fn()
            except BaseException as e:  # noqa: BLE001 — surfaced at join
                with self._cv:
                    if self._error is None:
                        self._error = e
                    self._stats["failed"] += 1
            finally:
                with self._cv:
                    self._stats["busy_s"] += time.monotonic() - t0
                    self._stats["completed"] += 1
                    self._outstanding -= 1
                    self._cv.notify_all()


def writer_from_config(async_io: Optional[bool] = None, *,
                       slots: int = 2, name: str = "stpu-async-io"):
    """The knob resolver every component shares: kwarg wins, else the
    ``STpu_ASYNC_IO`` env (""/"0" = off). Returns an armed
    ``AsyncWriter`` or the inline ``SyncWriter``."""
    if resolve_async_io(async_io):
        return AsyncWriter(slots=slots, name=name)
    return SyncWriter()
