"""check_stream: one exhaustive check at a stated size, from its
initial state, through the user's entry point.

The check is ``model.checker().spawn_tpu_bfs(fused=True, **sizes)``
with the configuration's engine sizes. Set-up ends, and the window
opens, at the check's first processed dispatch: by then the process
has imported, built the model, compiled (or loaded from the persistent
cache) every program the check runs, and seeded the device arena. The
window closes at the first processed dispatch at or after
``--seconds``, or at the check's last dispatch where it ends first.
``states_per_s`` is the unique states admitted between the two
dispatches over the seconds between them.

After the window the check is stopped (``preempt``), and at every
dispatch it processed its expanded rows, unique states and generated
states must equal those of the plain reference named by the
configuration (``benchmark/reference``) after as many waves of the
configured batch; at its last dispatch its discoveries must be the
reference's, each at the same depth. The numbers compared are the
largest differences; each has the limit 0.
"""

from __future__ import annotations

import os
import shutil
import time
import traceback

#: how long a check may take to reach its first dispatch (compiles)
FIRST_DISPATCH_TIMEOUT_S = 1100.0


class CheckFailed(RuntimeError):
    """A check whose engine raised, or that gave no window to measure."""


def build_model(config: dict):
    """The configuration's model from the program's model registry, and
    its program-cache key."""
    from stateright_tpu.service.registry import default_registry

    reg = default_registry()
    model, params = reg.build(config["model"], config["params"])
    return model, reg.program_key(config["model"], params)


def closing_index(log: list, start: int, t_close: float):
    """The first dispatch at or after ``start`` processed at or after
    ``t_close``, else None."""
    for i in range(start, len(log)):
        if log[i]["t"] >= t_close:
            return i
    return None


class Window:
    """Drives one check from ``spawn()`` through one measured window,
    which covers the dispatches ``log[1..last]`` of its log;
    ``admitted`` is the unique states admitted inside it."""

    def __init__(self, spawn, seconds: float, poll_s: float = 0.02,
                 clock=time.monotonic, sleep=time.sleep, span=None):
        self.spawn, self.seconds, self.poll_s = spawn, seconds, poll_s
        self.clock, self.sleep = clock, sleep
        self.span = span or _no_span
        self.checker = self.last = None
        self.t_open = self.t_close = None
        self.admitted = 0
        self.compile_s = 0.0

    def open(self):
        """Spawns the check and waits for its first dispatch."""
        with self.span("bench.spawn"):
            checker = self.spawn()
        deadline = self.clock() + FIRST_DISPATCH_TIMEOUT_S
        while not checker.dispatch_log:
            if checker.is_done():
                _join(checker)  # raises the engine's error, if any
                raise CheckFailed("the check ended before its first "
                                  "dispatch was processed")
            if self.clock() > deadline:
                raise CheckFailed("no dispatch within "
                                  f"{FIRST_DISPATCH_TIMEOUT_S} s")
            self.sleep(self.poll_s)
        first = checker.dispatch_log[0]
        self.checker = checker
        self.t_open = first["t"]
        self.admitted = -first["unique"]
        self.compile_s = checker.compile_sec
        return self.t_open

    def run(self, on_poll=None):
        """Polls until the closing dispatch, the first at or after
        ``seconds`` or the check's last, whichever comes first; returns
        the window's seconds. ``on_poll(elapsed, log)`` runs at each
        poll."""
        t_close = self.t_open + self.seconds
        start = 1
        while True:
            done = self.checker.is_done()  # before the scan: none missed
            log = self.checker.dispatch_log
            idx = closing_index(log, start, t_close)
            if idx is None and done:
                if len(log) < 2:
                    raise CheckFailed("the check ended at the dispatch "
                                      "that opened the window")
                idx = len(log) - 1
            if idx is not None:
                self.last = idx
                self.t_close = log[idx]["t"]
                self.admitted += log[idx]["unique"]
                return self.t_close - self.t_open
            start = max(start, len(log))
            if on_poll is not None:
                on_poll(self.clock() - self.t_open, log)
            with self.span("bench.wait"):
                self.sleep(self.poll_s)

    def entries(self) -> list:
        """The dispatches inside the window, in order."""
        return self.checker.dispatch_log[1:self.last + 1]


def _no_span(_name):
    import contextlib

    return contextlib.nullcontext()


def _join(checker):
    try:
        checker.join()
    except Exception as e:  # noqa: BLE001 — reported as a failed check
        raise CheckFailed(f"{type(e).__name__}: {e}") from e


# -- Comparison --------------------------------------------------------------


def _depths(checker) -> dict:
    return {name: len(path.into_states()) - 1
            for name, path in checker.discoveries().items()}


def _diff(got: dict, want: dict, keys) -> dict:
    out = {k: abs(int(got[k]) - int(want[k])) for k in keys}
    gd, wd = got["discoveries"], want["discoveries"]
    out["disc_diff"] = sum(1 for n in set(gd) | set(wd)
                           if gd.get(n) != wd.get(n))
    return out


def compare_prefix(checker, ref, batch: int) -> dict:
    """Every processed dispatch of ``checker`` against the reference
    after as many waves of ``batch`` rows."""
    worst = {"head": 0, "unique": 0, "states": 0}
    waves = head = 0
    for e in checker.dispatch_log:
        waves += e["waves"]
        head += e["rows"]
        want = ref.waves(batch, waves)
        got = {"head": head, "unique": e["unique"], "states": e["states"]}
        for k in worst:
            worst[k] = max(worst[k], abs(got[k] - want[k]))
    got = {"head": head, "unique": checker.unique_state_count(),
           "states": checker.state_count(), "discoveries": _depths(checker)}
    last = _diff(got, ref.waves(batch, waves),
                 ("head", "unique", "states"))
    return {"head_diff": max(worst["head"], last["head"]),
            "unique_diff": max(worst["unique"], last["unique"]),
            "states_diff": max(worst["states"], last["states"]),
            "disc_diff": last["disc_diff"]}


def _lookahead(log: list) -> int:
    """Waves done so far plus two more dispatches: about where a check
    that was asked to stop will stop."""
    waves = [e["waves"] for e in list(log)]
    return sum(waves) + 2 * max(waves, default=0)


#: the numbers compared; ``errors`` is 1 where the engine raised or its
#: answers could not be read (a discovery path that does not replay,
#: for one)
COMPARED = ("head_diff", "unique_diff", "states_diff", "disc_diff",
            "errors")


def judge(window: Window, config: dict, make_reference) -> dict:
    """Stops the check, compares every dispatch it processed, and
    returns the driver's verdict keys."""
    batch = config["spawn"]["batch_size"]
    checker = window.checker
    checker.preempt()
    worst = dict.fromkeys(COMPARED, 0)
    try:
        ref = make_reference()
        # The reference's search overlaps the engine's drain of the
        # dispatches it still has in flight.
        ref.waves(batch, _lookahead(checker.dispatch_log))
        _join(checker)
        worst.update(compare_prefix(checker, ref, batch))
    except Exception:  # noqa: BLE001 — an answer that cannot be read
        traceback.print_exc()
        worst["errors"] = 1
    failed = int(any(worst.values()))
    compared = {k: {"value": v, "limit": 0} for k, v in worst.items()}
    return {"correct": failed == 0, "attempted": 1, "failed": failed,
            "compared": compared}


# -- The run -----------------------------------------------------------------


def run(ctx: dict) -> dict:
    """One run of a check-stream cell (see the module docstring)."""
    import jax

    args, config, traffic = ctx["args"], ctx["config"], ctx["traffic"]
    model, key = build_model(config)
    from stateright_tpu.jit_cache import WaveProgramCache

    cache = WaveProgramCache()

    def spawn():
        return model.checker().spawn_tpu_bfs(
            fused=True, program_cache=cache, program_key=key,
            **config["spawn"])

    tracer = _Tracer(ctx["out_dir"], traffic) if args.trace else None
    win = Window(spawn, args.seconds, traffic["poll_s"],
                 span=jax.profiler.TraceAnnotation)
    t_open = win.open()
    if tracer is not None:
        tracer.t_open = t_open
    setup_s = t_open - ctx["t0"]
    window_s = win.run(on_poll=tracer.poll if tracer else None)
    if tracer is not None:
        tracer.stop(win.checker.dispatch_log[:win.last + 1])
    memory_peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use",
                                                      0))
                      for d in ctx["devices"])
    entries = win.entries()
    ref_cfg = config["reference"]
    verdict = judge(win, config, lambda: ctx["load_plugin"](
        "reference", ref_cfg["module"]).make(ref_cfg["params"]))
    res = dict(verdict,
               end_to_end={"setup_s": setup_s,
                           "states_per_s": win.admitted / window_s},
               window={"entries": entries, "seconds": window_s,
                       "admitted": win.admitted},
               compile_s=win.compile_s,
               device={"memory_peak_bytes": memory_peak})
    red = tracer.reduce() if tracer is not None else None
    if red is not None:
        res["trace"] = red
        res["device"].update(busy_s=red["busy_s"],
                             window_s=red["window_s"])
        res["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    return res


class _Tracer:
    """One profiler stretch inside the measured window that holds a
    dispatch boundary, where the program's host loop takes its turn.
    Once the window has two processed dispatches, the next is expected
    one interval after the last; the stretch starts ``trace_s`` before
    that and stops ``trace_tail_s`` after the next dispatch is
    processed, after ``trace_max_s`` at most, or when the window
    closes. (A paxos-3 trace overflows the profiler's buffer after
    about 3 s.)"""

    def __init__(self, out_dir: str, traffic: dict):
        self.dir = os.path.join(out_dir, "trace")
        shutil.rmtree(self.dir, ignore_errors=True)
        self.lead, self.tail, self.most = (
            traffic["trace_s"], traffic["trace_tail_s"],
            traffic["trace_max_s"])
        self.t_open = None
        self.span = None
        self.t_start = self.t_stop = None
        self.n_start = None
        self.dispatches = 0  # processed inside the stretch

    def poll(self, elapsed: float, log: list):
        import jax

        if self.t_start is None and len(log) >= 2:
            due = 2 * log[-1]["t"] - log[-2]["t"] - self.t_open
            if elapsed >= due - self.lead:
                jax.profiler.start_trace(self.dir)
                self.span = jax.profiler.TraceAnnotation("bench.traced")
                self.span.__enter__()
                self.t_start, self.n_start = elapsed, len(log)
        elif self.span is not None:
            seen = len(log) > self.n_start and (
                elapsed >= log[self.n_start]["t"] - self.t_open
                + self.tail)
            if seen or elapsed >= self.t_start + self.most:
                self.stop(log)

    def stop(self, log: list):
        import jax

        if self.span is not None:
            self.span.__exit__(None, None, None)
            self.span = None
            jax.profiler.stop_trace()
            self.dispatches = len(log) - self.n_start

    def reduce(self):
        """The trace's numbers; None when the window closed before the
        traced stretch began."""
        from benchmark import trace_reduce

        if self.t_start is None:
            return None
        red = trace_reduce.reduce(trace_reduce.newest_xplane(self.dir))
        red["dispatches"] = self.dispatches
        return red
