"""The harness on the CPU: cells, configurations and metrics found by
name, the window's edges and credit, the result line, the refusal to
run without a TPU, and a cell added by files alone."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmark import run
from benchmark.drivers import check_stream as cs

from bench_helpers import ROOT, small_config


def _spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


# -- Found by name -----------------------------------------------------------


@pytest.mark.parametrize("cell", [w["name"] for w in _spec()["workloads"]])
def test_every_cell_resolves_by_name(cell):
    plan = run.resolve(_spec(), cell)
    assert plan["cell"]["name"] == cell
    assert callable(run.load_plugin(
        "drivers", plan["traffic"]["driver"]).run)
    ref = plan["config"]["reference"]
    assert callable(run.load_plugin("reference", ref["module"]).make)
    names = {m["name"] for m in plan["end_to_end"]}
    assert "setup_s" in names and len(names) >= 2
    assert plan["per_layer"]
    for m in plan["per_layer"]:
        assert callable(run.load_plugin("metrics", m["name"]).read)
        assert m["moves"] in names


def test_configuration_files_keep_their_sizes():
    spec = _spec()
    for entry in spec["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == entry["name"]
        assert cfg["reduced"] == entry["reduced"] == []
        spawn = cfg["spawn"]
        # pre-sized: the whole space plus one wave fits, so nothing grows
        fan = {"twopc": 2 + 5 * cfg["params"].get("rm_count", 0),
               "paxos": 18}[cfg["model"]]
        wave = spawn["batch_size"] * fan
        assert (cfg["full_space"]["unique"] + wave
                <= spawn["table_capacity"] // 2)
        assert (cfg["full_space"]["unique"] + wave
                <= spawn["arena_capacity"])


def test_unknown_cell_is_refused():
    with pytest.raises(run.BenchError, match="no cell"):
        run.resolve(_spec(), "no-such-cell")


# -- The window --------------------------------------------------------------


class FakeChecker:
    """Dispatch records that appear as the fake clock passes their
    time; ``unique`` at the end of the check."""

    def __init__(self, clock, records, unique):
        self.clock, self.records, self.unique = clock, records, unique
        self.compile_sec = 1.5

    @property
    def dispatch_log(self):
        return [r for r in self.records if r["t"] <= self.clock.now]

    def is_done(self):
        return self.clock.now >= self.records[-1]["t"]

    def join(self):
        return self

    def unique_state_count(self):
        return self.unique


class Clock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def sleep(self, s):
        self.now += s


def _window(records, unique, seconds):
    clock = Clock()
    win = cs.Window(lambda: FakeChecker(clock, records, unique), seconds,
                    poll_s=0.1, clock=clock, sleep=clock.sleep)
    win.open()
    return win, win.run()


def _recs(*pairs):
    return [{"t": t, "unique": u} for t, u in pairs]


def test_window_closes_on_a_dispatch_boundary():
    win, seconds = _window(_recs((1, 10), (2, 25), (3.5, 40), (5, 60)),
                           60, 2.0)
    # opens at the first dispatch (t=1), closes at the first at or
    # after t=3, which is t=3.5: not at 3, and not the last one
    assert (win.t_open, win.t_close) == (1, 3.5)
    assert seconds == pytest.approx(2.5)
    assert win.admitted == 40 - 10
    assert [e["t"] for e in win.entries()] == [2, 3.5]
    assert win.compile_s == 1.5


def test_window_closes_when_the_check_ends():
    win, seconds = _window(_recs((1, 10), (2, 30), (2.5, 50)), 50, 5.0)
    # the check's last dispatch closes the window; nothing respawns
    assert (win.t_open, win.t_close) == (1, 2.5)
    assert win.admitted == 50 - 10
    assert [e["t"] for e in win.entries()] == [2, 2.5]


def test_a_check_that_ends_at_its_first_dispatch_is_refused():
    with pytest.raises(cs.CheckFailed, match="opened the window"):
        _window(_recs((1, 10)), 10, 5.0)


# -- The traced stretch ------------------------------------------------------


@pytest.fixture
def tracer(monkeypatch, tmp_path):
    """A ``_Tracer`` whose profiler calls are only recorded."""
    import jax

    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"))
    t = cs._Tracer(str(tmp_path), {"trace_s": 1.0, "trace_tail_s": 0.25,
                                   "trace_max_s": 2.0})
    t.t_open = 100.0
    return t, calls


def test_the_traced_stretch_holds_a_dispatch_boundary(tracer):
    t, calls = tracer
    log = _recs((100.0, 1))
    t.poll(3.0, log)                      # one dispatch: no interval yet
    log += _recs((103.0, 2))              # due next at 106 (elapsed 6)
    t.poll(4.9, log)
    assert calls == []
    t.poll(5.0, log)                      # trace_s before it is due
    assert calls == ["start"] and t.t_start == 5.0
    t.poll(5.9, log)
    log += _recs((106.25, 3))             # processed late
    t.poll(6.4, log)                      # not yet trace_tail_s after it
    assert calls == ["start"]
    t.poll(6.5, log)
    assert calls == ["start", "stop"] and t.dispatches == 1
    t.poll(9.0, log + _recs((109.0, 4)))  # one stretch per run
    assert calls == ["start", "stop"]


def test_the_traced_stretch_has_a_longest(tracer):
    t, calls = tracer
    log = _recs((100.0, 1), (101.0, 2))
    t.poll(1.0, log)                      # due at 2: starts at once
    t.poll(2.9, log)
    assert calls == ["start"]
    t.poll(3.0, log)                      # trace_max_s and no dispatch
    assert calls == ["start", "stop"] and t.dispatches == 0


# -- A whole run on the CPU --------------------------------------------------


def test_a_run_is_correct_and_its_line_has_only_the_keys(harness):
    ctx, res = harness(small_config(3, batch=8), seconds=0.3)
    assert res["correct"] and res["failed"] == 0
    assert res["attempted"] == 1
    assert all(c == {"value": 0, "limit": 0}
               for c in res["compared"].values())
    e2e = res["end_to_end"]
    assert e2e["states_per_s"] > 0 and e2e["setup_s"] > 0
    metrics = {m: {"value": v, "unit": "x"} for m, v in e2e.items()}
    device = dict(run.device_info(ctx["devices"]), **res["device"])
    line = run.result_line(res, metrics, device, trace=False)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert set(device) == {"platform", "kind", "count",
                           "memory_peak_bytes"}
    traced = run.result_line(dict(res, breakdown={"device_ops": [],
                                                  "idle_gaps": []}),
                             metrics, device, trace=True)
    assert list(traced)[-2:] == ["breakdown", "compared"]
    json.dumps(line)


def test_per_layer_readers_on_a_run(harness):
    ctx, res = harness(small_config(4, batch=16), seconds=0.3)
    plan_metrics = [m for m in _spec()["per_layer"]]
    got = run.read_per_layer(plan_metrics, dict(ctx, **res))
    # no trace on the CPU: the trace readers find nothing and stay out
    assert set(got) == {"compile_s", "batch_occupancy", "dedup_collapse"}
    assert 0 < got["batch_occupancy"]["value"] <= 1
    assert 0 < got["dedup_collapse"]["value"] <= 1


@pytest.mark.parametrize("where", ["repo", "bench_only"])
def test_run_py_refuses_without_a_tpu(tmp_path, where):
    root = ROOT
    if where == "bench_only":
        # only BENCHMARK.json and the files under paths: no program
        root = str(tmp_path)
        for p in _spec()["paths"]:
            shutil.copytree(os.path.join(ROOT, p), os.path.join(root, p),
                            ignore=shutil.ignore_patterns("__pycache__"))
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", "paxos3-check",
         "--seed", "3000000000", "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "no TPU" in proc.stderr


# -- Adding by files ---------------------------------------------------------


def test_a_cell_config_and_metric_are_added_by_files_alone(tmp_path,
                                                          harness):
    """A copy of the benchmark gains a configuration, a traffic mix, a
    per-layer metric and a cell as new files plus BENCHMARK.json
    entries; the copy's own run.py finds and runs them, and no file
    that was there changed."""
    for p in ("benchmark",):
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    before = {str(p): p.read_bytes()
              for p in (tmp_path / "benchmark").rglob("*") if p.is_file()}
    spec = _spec()
    cfg = small_config(4, batch=16)
    (tmp_path / "benchmark/configs/2pc-4.json").write_text(json.dumps(cfg))
    traffic = json.loads((tmp_path / "benchmark/traffic/check_stream.json")
                         .read_text())
    (tmp_path / "benchmark/traffic/check_stream_fast.json").write_text(
        json.dumps(dict(traffic, poll_s=0.01)))
    (tmp_path / "benchmark/metrics/window_dispatches.py").write_text(
        "def read(ctx):\n    return len(ctx['window']['entries']) or None\n")
    spec["configs"].append({"name": "2pc-4", "source": "x",
                            "file": "benchmark/configs/2pc-4.json",
                            "reduced": [], "why": "test"})
    spec["workloads"].append({"name": "twopc4-check", "config": "2pc-4",
                              "traffic": "check_stream_fast", "chips": 1,
                              "why": "test"})
    spec["end_to_end"][0]["workloads"].append("twopc4-check")
    spec["per_layer"].append({"name": "window_dispatches",
                              "unit": "count", "better": "higher",
                              "source": "program_counter",
                              "layer": "host loop",
                              "moves": "states_per_s",
                              "workloads": ["twopc4-check"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    mod_spec = importlib.util.spec_from_file_location(
        "copied_run", tmp_path / "benchmark/run.py")
    copied = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(copied)
    plan = copied.resolve(spec, "twopc4-check", root=str(tmp_path))
    assert plan["config"] == cfg
    assert plan["traffic"]["poll_s"] == 0.01
    assert [m["name"] for m in plan["per_layer"]] == ["window_dispatches"]

    ctx, res = harness(plan["config"], seconds=0.3)
    assert res["correct"]
    got = copied.read_per_layer(plan["per_layer"], dict(ctx, **res))
    assert got["window_dispatches"]["value"] >= 1
    after = {str(p): p.read_bytes()
             for p in (tmp_path / "benchmark").rglob("*") if p.is_file()
             and "__pycache__" not in str(p)}
    assert all(after[k] == v for k, v in before.items())
