"""The ``ycsb_a_zipf`` cell, by hand (``pytest chipbench/tests``; tier-1
collects ``tests/`` only): rehearsed end to end on the CPU, fault
injection turns ``correct`` false, and the rule the cell came in under —
a PR that is not of kind ``benchmark`` edits no file the benchmark had.
"""

import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)

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
    # no latency_p95_ms here: the cell's own file leaves it out
    assert set(out["metrics"]) == {"calls_per_s", "latency_p50_ms",
                                   "setup_s"}
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


def test_a_files_only_pr_edits_no_file_the_benchmark_had():
    """Against the parent commit: a PR of any kind but ``benchmark`` may
    add files under chipbench/ and entries to BENCHMARK.json, and edits or
    deletes nothing the benchmark had (empty once the PR is the HEAD
    commit). The kind is in the heading of ISSUE.md."""
    def git(*a: str) -> str:
        return subprocess.run(["git", *a], capture_output=True, text=True,
                              cwd=ROOT, check=True).stdout
    try:
        changed = git("diff", "--name-status", "HEAD", "--", "chipbench")
        before = json.loads(git("show", "HEAD:BENCHMARK.json"))
    except (subprocess.CalledProcessError, FileNotFoundError) as e:
        pytest.skip(f"not a git checkout: {e}")
    try:
        with open(os.path.join(ROOT, "ISSUE.md")) as f:
            heading = f.readline()
    except FileNotFoundError:
        heading = ""
    if "[benchmark]" in heading:
        pytest.skip("a PR of kind benchmark may edit the benchmark")
    edited = [x for x in changed.splitlines() if not x.startswith("A")]
    assert not edited, edited
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        now = json.load(f)

    def grown(was: list, is_now: list) -> bool:
        """Entries only added; a metric's ``workloads`` list may gain the
        new cells' names at its end; nothing else of an entry moves."""
        def same(a: dict, b: dict) -> bool:
            la, lb = a.get("workloads"), b.get("workloads")
            return {**a, "workloads": None} == {**b, "workloads": None} \
                and (la is None) == (lb is None) \
                and (la is None or lb[:len(la)] == la)
        return len(is_now) >= len(was) and all(map(same, was, is_now))

    for k in ("command", "paths", "run_seconds"):
        assert now[k] == before[k], k
    assert len(now["end_to_end"]) == len(before["end_to_end"])
    for k in ("end_to_end", "configs", "workloads", "per_layer"):
        assert grown(before[k], now[k]), k
