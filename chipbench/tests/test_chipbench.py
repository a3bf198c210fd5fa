"""By-hand tests of the benchmark harness (``pytest chipbench/tests``; not
collected by the repo's tier-1 run, which collects ``tests/`` only).

Everything here runs on the CPU: the ``--rehearse-cpu`` command end to end
at a tiny size, the reductions against fixtures, and the data-driven rule
(a new cell, configuration and per-layer metric are files, found by name).
Nothing it prints is a measurement.
"""

import asyncio
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import loadgen  # noqa: E402
import peaks  # noqa: E402
import trace_reduce  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device",
               "compared"}


def run_cell(workload: str, *extra: str, root: str = ROOT, trace: int = 0,
             seconds: float = 1.5) -> tuple[int, dict, list[dict]]:
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)  # --rehearse-cpu sets it itself
    p = subprocess.run(
        [sys.executable, os.path.join(root, "chipbench", "run.py"),
         "--workload", workload, "--seed", "3000000019", "--seconds",
         str(seconds), "--trace", str(trace), "--rehearse-cpu", *extra],
        capture_output=True, text=True, timeout=300, cwd=root, env=env)
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.strip()]
    assert lines, p.stderr[-2000:]
    return p.returncode, lines[-1], lines[:-1]


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("cell", [w["name"] for w in manifest()["workloads"]])
def test_rehearsal_prints_a_correct_last_line(cell):
    rc, last, lines = run_cell(cell)
    assert rc == 0 and set(last) == RESULT_KEYS
    assert list(last)[-1] == "compared"   # each number beside its limit
    assert all(c["value"] <= c["limit"] for c in last["compared"].values())
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert last["device"]["platform"] == "cpu"  # never passes for a chip
    # an end-to-end metric that lists its cells exists in those alone
    want = {m["name"] for m in manifest()["end_to_end"]
            if cell in m.get("workloads", [cell])}
    assert set(last["metrics"]) == want and "setup_s" in want
    assert all(v["value"] > 0 for v in last["metrics"].values())
    # the two populations: every request is in one of them, and a kind
    # that marks no record hot reads the same numbers both ways
    c = next(ln for ln in lines if ln.get("phase") == "clients")
    assert 0 <= c["hot_requests"] <= c["requests_in_window"]
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        marks = "hot_in_flight" in json.load(f)["params"]
    assert (c["hot_requests"] > 0) == marks
    if not marks:
        assert c["latency_ms_cold"] == {
            q: c["latency_ms"][q] for q in c["latency_ms_cold"]}
        assert set(c["latency_ms_hot"].values()) == {None}


@pytest.mark.parametrize("cell", [w["name"] for w in manifest()["workloads"]])
def test_traced_rehearsal_reports_the_cells_layer_metrics(cell):
    rc, last, _ = run_cell(cell, trace=1, seconds=2.0)
    assert rc == 0 and last["correct"] is True
    declared = {m["name"] for m in manifest()["per_layer"]
                if cell in m.get("workloads", [cell])}
    # device metrics with no CPU meaning are left out, never faked
    assert set(last["metrics"]) <= declared
    assert declared - set(last["metrics"]) <= {"kernels.hbm_roofline_pct"}
    assert last["device"]["busy_s"] > 0 and last["device"]["window_s"] > 0
    assert set(last["breakdown"]) == {"device_ops", "idle_gaps"}
    assert 0 < len(last["breakdown"]["device_ops"]) <= 10


@pytest.mark.parametrize("fault,cell", [("reply", "ping_closed100"),
                                        ("row", "ping_closed100")])
def test_a_corrupted_reference_turns_correct_false(fault, cell):
    rc, last, _ = run_cell(cell, "--inject-fault", fault, seconds=1.0)
    assert last["correct"] is False and rc != 0


def test_without_a_chip_the_command_fails_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         manifest()["workloads"][0]["name"], "--seconds", "1"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    assert p.returncode != 0
    assert not any('"correct"' in ln for ln in p.stdout.splitlines())


# -- the open-loop generator ---------------------------------------------

class SlowTraffic:
    """Every request takes ``service`` seconds on the one caller slot."""
    n_callers, calls_per_request = 1, 1

    def __init__(self, service: float) -> None:
        self.service = service

    async def request(self, client, slot):
        await asyncio.sleep(self.service)
        return 1, 0, 0


def test_open_loop_times_from_due_time_and_reports_lateness():
    import time

    async def go():
        t0 = time.monotonic() + 0.05
        return t0, await loadgen.drive(SlowTraffic(0.02), None, "open", t0,
                                       1.0, rate=100.0, seed=7)
    t0, (rec, extra) = asyncio.run(go())
    c = rec.columns()
    # arrivals are the seed's Poisson schedule, whatever the system did
    rng = np.random.default_rng([7, 0xA221])
    due = t0 + np.cumsum(rng.exponential(1 / 100.0, size=216))
    np.testing.assert_allclose(c["due"], due[:len(c["due"])])
    # offered 100/s, served 50/s: the generator runs late and says so,
    # and latency counts the wait from the due time, not from the send
    late = c["send"] - c["due"]
    assert (late >= 0).all() and late.mean() > 0.1
    assert ((c["done"] - c["due"]) >= (c["done"] - c["send"])).all()
    assert extra["unsent"] > 0 and extra["due"] > len(c["due"])


class HotSlotTraffic(SlowTraffic):
    """Two callers; the kind says caller 1's requests are aimed at a hot
    record."""
    n_callers = 2

    def hot_of(self, slot: int) -> int:
        return slot


def test_the_load_generator_asks_the_kind_which_requests_were_hot():
    import time

    async def go(traffic):
        t0 = time.monotonic() + 0.05
        rec, _ = await loadgen.drive(traffic, None, "closed", t0, 0.3,
                                     rate=None, seed=7)
        return rec.columns()
    c = asyncio.run(go(HotSlotTraffic(0.02)))
    assert 0.4 < c["hot"].mean() < 0.6 and set(c["hot"]) == {0.0, 1.0}
    # a kind without ``hot_of`` marks nothing
    assert not asyncio.run(go(SlowTraffic(0.02)))["hot"].any()


def test_closed_loop_sends_the_next_request_on_the_reply():
    import time

    async def go():
        t0 = time.monotonic() + 0.05
        return await loadgen.drive(SlowTraffic(0.02), None, "closed", t0,
                                   0.5, rate=None, seed=7)
    rec, _ = asyncio.run(go())
    c = rec.columns()
    assert 15 <= rec.n <= 26
    assert (c["send"] == c["due"]).all()
    assert (c["send"][1:] >= c["done"][:-1]).all()


# -- driven by data --------------------------------------------------------

def _digest(root: str) -> dict:
    out = {}
    for d, _dirs, files in os.walk(root):
        if "__pycache__" in d:
            continue
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_a_new_cell_config_and_metric_are_files_found_by_name(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(os.path.join(ROOT, "orleans_tpu"), root / "orleans_tpu")
    before = _digest(root / "chipbench")
    with open(os.path.join(BENCH, "configs", "ping-10k.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "ping-2k"
    cfg["rehearse"]["grains"][0]["dense"] = 2000
    cfg["rehearse"]["capacity_per_shard"] = 2048
    cfg["rehearse"]["population"]["dense"] = 2000
    (root / "chipbench/configs/ping-2k.json").write_text(json.dumps(cfg))
    with open(os.path.join(BENCH, "workloads", "ping_closed100.json")) as f:
        wl = json.load(f)
    wl.update(name="ping_open_new", config="ping-2k", loop="open", rate=500.0)
    (root / "chipbench/workloads/ping_open_new.json").write_text(
        json.dumps(wl))
    (root / "chipbench/layer_metrics/engine.deferred_per_s.json").write_text(
        json.dumps({"name": "engine.deferred_per_s", "unit": "1/s",
                    "better": "lower", "source": "program_counter",
                    "layer": "engine queue / claim", "moves": "calls_per_s",
                    "cells": ["ping_open_new"], "reader": "counter_rate",
                    "args": {"counter": "rt.conflicts_deferred"}}))
    rc, last, lines = run_cell("ping_open_new", root=str(root), trace=1,
                               seconds=2.0)
    assert rc == 0 and last["correct"] is True
    assert "engine.deferred_per_s" in last["metrics"]
    # no manifest, no list to be on: a new cell reports every end-to-end metric
    e2e = next(ln for ln in lines if ln.get("phase", "").startswith(
        "end-to-end"))
    assert {"calls_per_s", "latency_p50_ms", "latency_p95_ms",
            "setup_s"} <= set(e2e)
    env = next(ln for ln in lines if ln.get("phase") == "environment")
    assert env["config"]["population"]["dense"] == 2000
    clients = next(ln for ln in lines if ln.get("phase") == "clients")
    assert clients["loop"] == "open"
    after = _digest(root / "chipbench")
    assert {k: v for k, v in after.items() if k in before} == before
    # and an old cell does not pick up the new cell's metric
    rc, last, _ = run_cell("ping_closed100", root=str(root), trace=1,
                           seconds=1.0)
    assert rc == 0 and "engine.deferred_per_s" not in last["metrics"]


def test_run_py_names_no_cell_config_or_metric():
    with open(os.path.join(BENCH, "run.py")) as f:
        src = f.read()
    m = manifest()
    names = [x["name"] for k in ("configs", "workloads", "per_layer")
             for x in m[k]]
    names += [w["traffic"] for w in m["workloads"]]
    names += [os.path.splitext(f)[0] for k in ("traffic", "readers", "apps",
                                               "references")
              for f in os.listdir(os.path.join(BENCH, k)) if f.endswith(".py")]
    assert not [n for n in names if n in src]


def test_manifest_matches_the_files():
    m = manifest()
    for c in m["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            d = json.load(f)
        assert d["name"] == c["name"] and d["source"] == c["source"]
        assert d["reduced"] == c["reduced"]
    for w in m["workloads"]:
        with open(os.path.join(BENCH, "workloads", w["name"] + ".json")) as f:
            d = json.load(f)
        assert (d["config"], d["traffic"], d["why"]) == \
            (w["config"], w["traffic"], w["why"])
        with open(os.path.join(BENCH, "configs", d["config"] + ".json")) as f:
            assert json.load(f)["chips"] == w["chips"]
    files = sorted(os.listdir(os.path.join(BENCH, "layer_metrics")))
    assert files == sorted(x["name"] + ".json" for x in m["per_layer"])
    for x in m["per_layer"]:
        with open(os.path.join(BENCH, "layer_metrics",
                               x["name"] + ".json")) as f:
            d = json.load(f)
        for k in ("unit", "better", "source", "layer", "moves"):
            assert d[k] == x[k], (x["name"], k)
        assert d["cells"] == x.get("workloads", "all")
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           d["reader"] + ".py"))


# -- the trace reduction -----------------------------------------------------

def synthetic_planes() -> list[dict]:
    """One device plane: 10 ticks 1 ms apart, each a 30 us fusion and a
    10 us copy overlapping its last 5 us; a host thread whose ``tick``
    event spans each op pair and whose ``flush`` event spans the last gap."""
    ops, host = [], []
    for i in range(10):
        t = i * 1_000_000.0
        ops.append(("fusion.1", t, 30_000.0, False))
        ops.append(("copy.2", t + 25_000.0, 10_000.0, False))
        host.append(("tick", t - 5_000.0, 45_000.0, False))
    host.append(("flush", 8_100_000.0, 800_000.0, False))
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_step", 0.0, 35_000.0, False)]},
            {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "loop", "events": host}]},
    ]


def test_trace_reduce_on_a_synthetic_trace():
    r = trace_reduce.reduce(synthetic_planes(), window_s=0.01)
    assert r["busy_s"] == pytest.approx(10 * 35e-6)      # the union
    assert r["op_seconds"] == pytest.approx(10 * 40e-6)  # the plain sum
    assert r["idle_share"] == pytest.approx(1 - 350e-6 / 0.01)
    assert r["ops"] == 20 and r["device_planes"] == 1
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(300e-6)]
    assert r["device_ops"][1] == ["copy.2", pytest.approx(100e-6)]
    assert r["longest_gap_s"] == pytest.approx(965e-6)
    gaps = dict(r["idle_gaps"])
    assert gaps["flush"] == pytest.approx(965e-6)
    assert gaps["unattributed"] == pytest.approx(8 * 965e-6)


def test_trace_reduce_without_device_ops_returns_nothing():
    planes = [p for p in synthetic_planes() if p["name"] != "/device:TPU:0"]
    assert trace_reduce.reduce(planes, window_s=1.0) is None
    planes[0]["lines"][0]["events"].append(("dot.1", 0.0, 1000.0, True))
    r = trace_reduce.reduce(planes, window_s=1.0, cpu_fallback=True)
    assert r["busy_s"] == pytest.approx(1e-6)


RECORDED = os.path.join(HERE, "data", "recorded.xplane.pb")
EXPECTED = os.path.join(HERE, "data", "recorded.expected.json")


@pytest.mark.skipif(not os.path.isfile(RECORDED),
                    reason="no recorded chip trace small enough to keep")
def test_trace_reduce_on_the_recorded_chip_trace():
    with open(EXPECTED) as f:
        want = json.load(f)
    r = trace_reduce.reduce(trace_reduce.load(RECORDED), want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["idle_share"] == pytest.approx(want["idle_share"], rel=1e-9)
    assert r["ops"] == want["ops"]
    assert [n for n, _s in r["device_ops"]] == \
        [n for n, _s in want["device_ops"]]


# -- peaks -------------------------------------------------------------------

def test_peaks_unknown_accelerator_is_an_error():
    assert peaks.device_peaks("cpu", "cpu") is None
    assert peaks.device_peaks("tpu", "TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    assert "source" in peaks.DEVICE_PEAKS["TPU v5 lite"]
    with pytest.raises(KeyError):
        peaks.device_peaks("tpu", "TPU v9 imaginary")
