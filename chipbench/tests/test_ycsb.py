"""The ``ycsb_a_zipf`` cell, by hand (``pytest chipbench/tests``; tier-1
collects ``tests/`` only): rehearsed end to end on the CPU, fault
injection turns ``correct`` false, and the cell came in as files alone —
no file the benchmark already had differs from the parent commit's.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

NEW_FILES = {
    "apps/ycsb.py", "references/ycsb.py", "traffic/ycsb_ops.py",
    "configs/ycsb-1kb.json", "workloads/ycsb_a_zipf.json",
    "tests/test_ycsb.py", "readers/histogram_mean_or_zero.py",
    "layer_metrics/engine.deferred_share_pct.json",
    "layer_metrics/engine.defer_wait_ms.json",
    "layer_metrics/engine.claim_ms.json",
    "layer_metrics/engine.read_share_pct.json",
    "layer_metrics/wire.pickled_values_per_msg.json",
    "layer_metrics/wire.payload_bytes_per_msg.json",
}


def run(*argv: str, timeout: float = 600.0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                        "--workload", "ycsb_a_zipf", "--rehearse-cpu",
                        *argv], capture_output=True, text=True,
                       timeout=timeout, env=env, cwd=ROOT)
    lines = [json.loads(x) for x in p.stdout.splitlines() if x.strip()]
    return p.returncode, lines, p.stderr


def phase(lines: list, name: str) -> dict:
    return next(x for x in lines if x.get("phase") == name)


def test_rehearsal_end_to_end():
    rc, lines, err = run("--seed", "2800000033", "--seconds", "3")
    assert rc == 0, err[-2000:]
    out = lines[-1]
    assert out["correct"] and out["failed"] == 0 and out["attempted"] > 100
    assert set(out["metrics"]) == {"calls_per_s", "latency_p50_ms",
                                   "latency_p95_ms", "setup_s"}
    assert out["device"]["platform"] == "cpu"      # a rehearsal says so
    env = phase(lines, "environment")
    assert env["workload"]["client_procs"] == 2
    assert env["workload"]["callers"] == 32
    assert env["config"]["population"]["dense"] == 8192
    c = phase(lines, "correct")
    assert c["wrong_replies"] == 0 and c["rows"]["bad_rows"] == 0
    assert c["rows"]["rows"] > 50 and c["rows"]["excluded"] == 0
    assert c["storage"]["not_readable"] == 0
    assert c["storage"]["acknowledged_keys"] == c["rows"]["rows"]
    warm = phase(lines, "set-up")["warm_up_calls"]
    assert warm[0] > 0 and warm[1] == warm[2] == 0


def test_rehearsal_traced_prints_the_new_layer_metrics():
    rc, lines, err = run("--seed", "2800000039", "--seconds", "4",
                         "--trace", "1")
    assert rc == 0, err[-2000:]
    m = lines[-1]["metrics"]
    for name in ("engine.deferred_share_pct", "engine.defer_wait_ms",
                 "engine.claim_ms", "engine.read_share_pct",
                 "wire.pickled_values_per_msg",
                 "wire.payload_bytes_per_msg"):
        assert name in m, name
    assert m["wire.pickled_values_per_msg"]["value"] == 0
    assert 40 < m["engine.read_share_pct"]["value"] < 60
    assert m["engine.deferred_share_pct"]["value"] > 0   # hot keys collide
    assert m["tick.compiles_in_window"]["value"] == 0    # read's buckets too
    # a read's 1,000 B reply and an update's 100 B argument are on the wire
    assert 500 < m["wire.payload_bytes_per_msg"]["value"] < 1400
    b = phase(lines, "trace")["bytes_per_message"]
    assert b == {"row_read": 1028, "row_written": 1028, "args": 104,
                 "result": 4, "total": 2164}


def test_defer_wait_reads_zero_in_a_window_without_deferrals():
    """At its steady state the cell defers about one message a window, so
    a window with none is common: the metric must still be on the line."""
    sys.path.insert(0, BENCH)
    from readers import histogram_mean_or_zero as r
    name = "engine.defer_wait.seconds"
    assert r.read({"histograms": {}}, [name]) is None     # an older program
    quiet = {"histograms": {name: {"count": 0, "sum": 0.0}}}
    assert r.read(quiet, [name], scale=1000) == 0.0
    busy = {"histograms": {name: {"count": 4, "sum": 0.002}}}
    assert r.read(busy, [name], scale=1000) == pytest.approx(0.5)


@pytest.mark.parametrize("fault", ["reply", "row"])
def test_injected_fault_turns_correct_false(fault):
    rc, lines, _err = run("--seed", "5", "--seconds", "2",
                          "--inject-fault", fault)
    assert rc == 1
    assert lines[-1]["correct"] is False
    c = phase(lines, "correct")
    assert (c["wrong_replies"] > 0) if fault == "reply" \
        else (c["rows"]["bad_rows"] > 0)


def test_the_cell_came_in_as_files_alone():
    """Against the parent commit: nothing the benchmark had is edited or
    gone, and what is new under chipbench/ is this cell's."""
    def git(*a: str) -> str:
        return subprocess.run(["git", *a], capture_output=True, text=True,
                              cwd=ROOT, check=True).stdout
    try:
        parent = git("rev-parse", "HEAD").strip()
        changed = git("diff", "--name-status", parent, "--", "chipbench")
        untracked = git("ls-files", "--others", "--exclude-standard",
                        "--", "chipbench")
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        pytest.skip(f"not a git checkout: {e}")
    new = {x.split("\t")[1] for x in changed.splitlines()
           if x.startswith("A")} | set(untracked.split())
    edited = [x for x in changed.splitlines() if not x.startswith("A")]
    assert not edited, edited
    if new:   # (empty once the PR is the HEAD commit)
        assert {os.path.relpath(x, "chipbench") for x in new} == NEW_FILES
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert [w["name"] for w in bench["workloads"]][-1] == "ycsb_a_zipf"
    assert [c["name"] for c in bench["configs"]][-1] == "ycsb-1kb"
    assert bench["workloads"][0]["name"] == "presence_heartbeat"
    for m in sorted(NEW_FILES):
        if m.startswith("layer_metrics/"):
            with open(os.path.join(BENCH, m)) as f:
                d = json.load(f)
            entry = next(e for e in bench["per_layer"]
                         if e["name"] == d["name"])
            assert entry.get("workloads", "all") == d["cells"]
            for k in ("unit", "better", "source", "layer", "moves"):
                assert entry[k] == d[k]
