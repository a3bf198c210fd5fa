"""By-hand tests of the four-chip Presence cell (``pytest chipbench/tests``),
on the CPU: ``presence_4chip`` rehearsed end to end on four virtual devices
(``XLA_FLAGS=--xla_force_host_platform_device_count=4``; without the flag
the rehearsal fails at once, as the real run does on a host with fewer
than four chips), its two ``mesh.*`` metrics, fault injection, and that the
cell is data: its configuration names the app, the reference and the
traffic kind its one-chip twin runs. Nothing it prints is a measurement.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL, TWIN = "presence_4chip", "presence_heartbeat"
FOUR = "--xla_force_host_platform_device_count=4"


def run_cell(*extra: str, trace: int = 0, seconds: float = 1.5
             ) -> tuple[int, dict, list[dict]]:
    env = dict(os.environ, XLA_FLAGS=FOUR)
    env.pop("JAX_PLATFORMS", None)  # --rehearse-cpu sets it itself
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000019", "--seconds", str(seconds), "--trace",
         str(trace), "--rehearse-cpu", *extra],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.strip()]
    assert lines, p.stderr[-2000:]
    return p.returncode, lines[-1], lines[:-1]


def load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def test_rehearsal_serves_four_shards_and_is_correct():
    rc, last, records = run_cell()
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    assert last["device"] == {"platform": "cpu", "kind": "cpu", "count": 4,
                              "memory_peak_bytes": 0}
    correct = next(r for r in records if r.get("phase") == "correct")
    assert correct["rows"]["bad_rows"] == 0 and not correct["table_grew"]
    assert correct["storage"]["not_readable"] == 0
    assert correct["storage"]["acknowledged_keys"] == correct["rows"]["rows"]


def test_traced_rehearsal_reports_the_mesh_metrics():
    rc, last, _ = run_cell(trace=1, seconds=2.0)
    assert rc == 0 and last["correct"] is True
    m = {k: v["value"] for k, v in last["metrics"].items()}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {e["name"] for e in json.load(f)["per_layer"]
                    if CELL in e.get("workloads", [CELL])}
    assert set(m) <= declared
    assert declared - set(m) <= {"kernels.hbm_roofline_pct"}
    # 25 is an even job on four shards, 100 one shard doing everything
    assert 25.0 <= m["mesh.max_shard_share_pct"] <= 100.0
    assert 0.0 < m["mesh.filled_lanes_pct"] <= 100.0
    # lanes <= n_shards * max_shard_lanes <= slots, as shares of lanes
    assert m["mesh.filled_lanes_pct"] <= \
        100.0 * 25.0 / m["mesh.max_shard_share_pct"] + 1e-9
    assert m["staging.puts_per_job"] == 1.0
    assert m["tick.compiles_in_window"] == 0


@pytest.mark.parametrize("fault", ["reply", "row"])
def test_a_corrupted_reference_turns_correct_false(fault):
    rc, last, _ = run_cell("--inject-fault", fault, seconds=1.0)
    assert last["correct"] is False and rc != 0


def test_fewer_devices_than_chips_fails_at_once():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seconds", "1", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=300, cwd=ROOT, env=env)
    assert p.returncode != 0 and "asks for 4 chips" in p.stderr
    assert not any('"correct"' in ln for ln in p.stdout.splitlines())


def test_the_cell_is_its_twin_on_four_chips():
    """Same app, reference, generator, frame and row; four times the
    players, the callers and the calls in flight; nothing reduced."""
    wl, twin = load("workloads", CELL), load("workloads", TWIN)
    cfg, tcfg = load("configs", wl["config"]), load("configs", twin["config"])
    for k in ("app", "reference", "row", "storage", "capacity_per_shard"):
        assert cfg[k] == tcfg[k], k
    assert cfg["guarantees"][:len(tcfg["guarantees"])] == tcfg["guarantees"]
    assert cfg["chips"] == 4 and cfg["reduced"] == []
    assert cfg["population"] == {k: 4 * v
                                 for k, v in tcfg["population"].items()}
    assert cfg["grains"][0]["dense"] == cfg["chips"] * 1_000_000
    for k in ("loop", "client_procs", "generator", "params", "latency_of",
              "response_timeout"):
        assert wl[k] == twin[k], k
    assert wl["callers"] == 4 * twin["callers"]
    in_flight = wl["callers"] * wl["params"]["frame"]
    assert in_flight == wl["warm"]["max_tick_calls"] == 4096
    assert {k: v for k, v in wl["warm"].items() if k != "max_tick_calls"} == \
        {k: v for k, v in twin["warm"].items() if k != "max_tick_calls"}
    # a caller's partition: 62,500 dense and the twin's 2,048 GUID players
    assert cfg["population"]["hashed"] // wl["callers"] == \
        tcfg["population"]["hashed"] // twin["callers"] == 2048
