"""By-hand tests of the Chirper cell (``pytest chipbench/tests``; tier-1
collects ``tests/`` only), on the CPU: ``chirper_fanout_4chip`` rehearsed
end to end on four virtual devices (``--rehearse-cpu`` with
``XLA_FLAGS=--xla_force_host_platform_device_count=4``, as
``test_presence_4chip.py`` does), its ``exchange.*`` metrics, fault
injection, the bytes of a delivery, and the traffic kind against stores
that break a guarantee — one that loses a delivery, one that applies one
twice, one that acknowledges before it applies: each is found wrong, an
honest store passes. Nothing it prints is a measurement.
"""

import asyncio
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
CELL = "chirper_fanout_4chip"
FOUR = "--xla_force_host_platform_device_count=4"


def _load(kind: str, name: str):
    spec = importlib.util.spec_from_file_location(
        f"chirper_test_{kind}_{name}", os.path.join(BENCH, kind,
                                                    f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref_mod = _load("references", "chirper")
traffic_mod = _load("traffic", "chirper_ops")


def run_cell(*extra: str, trace: int = 0, seconds: float = 2.0):
    env = dict(os.environ, XLA_FLAGS=FOUR)
    env.pop("JAX_PLATFORMS", None)  # --rehearse-cpu sets it itself
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3600000019", "--seconds", str(seconds), "--trace",
         str(trace), "--rehearse-cpu", *extra],
        capture_output=True, text=True, timeout=600, cwd=ROOT, env=env)
    lines = [json.loads(ln) for ln in p.stdout.splitlines() if ln.strip()]
    assert lines, p.stderr[-2000:]
    return p.returncode, lines[-1], lines[:-1]


def test_rehearsal_serves_four_shards_and_is_correct():
    rc, last, records = run_cell()
    assert rc == 0 and last["correct"] is True and last["failed"] == 0
    assert last["device"]["count"] == 4
    assert set(last["metrics"]) == {"calls_per_s", "latency_p50_ms",
                                    "latency_p95_ms", "setup_s"}
    correct = next(r for r in records if r.get("phase") == "correct")
    assert correct["rows"]["rows"] > 100 and correct["rows"]["bad_rows"] == 0
    assert correct["storage"]["not_readable"] == 0
    assert correct["storage"]["acknowledged_keys"] == correct["rows"]["rows"]
    window = next(r for r in records if r.get("phase") == "window")
    assert window["compiles"] == 0


def test_traced_rehearsal_reports_the_exchange_metrics():
    rc, last, records = run_cell(trace=1, seconds=3.0)
    assert rc == 0 and last["correct"] is True
    m = {k: v["value"] for k, v in last["metrics"].items()}
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = {e["name"] for e in json.load(f)["per_layer"]
                    if CELL in e.get("workloads", [CELL])}
    assert set(m) <= declared
    # the two roofline shares are not measured on the CPU platform
    assert declared - set(m) <= {"kernels.hbm_roofline_pct",
                                 "exchange.hbm_roofline_pct"}
    assert m["exchange.dropped_pct"] == 0.0
    assert 55.0 < m["exchange.cross_shard_pct"] < 95.0
    assert 15.0 < m["exchange.deliveries_per_publish"] < 45.0
    assert m["exchange.rounds_per_job"] >= 1.0
    assert 0.0 < m["exchange.filled_lanes_pct"] <= 100.0
    assert m["exchange.ms_per_job"] > 0 and \
        m["exchange.collective_us_per_job"] > 0
    assert m["tick.compiles_in_window"] == 0
    gaps = [g[0] for g in last["breakdown"]["idle_gaps"]]
    assert any(g.startswith("otpu:") for g in gaps)


@pytest.mark.parametrize("fault", ["reply", "row"])
def test_a_corrupted_reference_turns_correct_false(fault):
    rc, last, _ = run_cell("--inject-fault", fault, seconds=1.5)
    assert last["correct"] is False and rc != 0


def test_a_delivery_moves_a_thousand_bytes():
    sys.path.insert(0, BENCH)
    sys.path.insert(0, ROOT)
    import exchange_bytes
    Account = _load("apps", "chirper").GRAINS["ChirperAccount"]
    assert exchange_bytes.delivery_bytes(Account, "receive") == {
        "payload_out": 320, "payload_in": 320, "keys": 8,
        "row_written": 320, "counters": 32, "total": 1000}


def test_the_manifest_holds_the_cell_as_the_issue_states_it():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bm = json.load(f)
    cell = next(w for w in bm["workloads"] if w["name"] == CELL)
    assert cell == {**cell, "config": "chirper-256k", "chips": 4,
                    "traffic": "publish10_read90_closed1024"}
    p95 = next(e for e in bm["end_to_end"] if e["name"] == "latency_p95_ms")
    assert p95["workloads"][-1] == CELL
    ex = [e for e in bm["per_layer"] if e["name"].startswith("exchange.")]
    assert len(ex) == 8 and all(e["workloads"] == [CELL] for e in ex)
    four = sum(w["chips"] == 4 for w in bm["workloads"])
    assert four * 2 <= len(bm["workloads"])


# ---------------------------------------------------------------------------
# the traffic kind against stores that break a guarantee
# ---------------------------------------------------------------------------

ACCOUNTS, TABLE = 64, [3, 4, 5, 6, 8, 10, 12, 16]


class Store:
    """A Chirper behind the client's interface; ``fault`` breaks it."""

    def __init__(self, fault: str | None) -> None:
        self.ref = ref_mod.Reference(5, ACCOUNTS, TABLE)
        self.fault, self.n, self.late = fault, 0, []

    def get_grain(self, _cls, key: int):
        return _Grain(self, key)

    def settle(self) -> None:
        for f, chirp in self.late:
            self.ref.timeline.setdefault(f, []).append(chirp)
        self.late = []

    def publish(self, key: int, chirp: bytes) -> int:
        self.n += 1
        to = self.ref.follower_keys(key)
        self.ref.seq[key] = self.ref.seq.get(key, 0) + 1
        for i, f in enumerate(to):
            hit = i == 0 and self.n % 3 == 0
            if self.fault == "lost" and hit:
                continue
            if self.fault == "early" and hit:
                self.late.append((f, chirp))   # acknowledged, not applied
                continue
            self.ref.timeline.setdefault(f, []).append(chirp)
            if self.fault == "twice" and hit:
                self.ref.timeline[f].append(chirp)
        return len(to)


class _Grain:
    def __init__(self, store: Store, key: int) -> None:
        self.store, self.key = store, key

    async def publish(self, chirp: bytes):
        return np.int32(self.store.publish(self.key, chirp))

    async def get_received(self, n: int):
        got, data = self.store.ref.get_received(self.key, n)
        return np.int32(got), np.frombuffer(data, np.uint8)


def _judge(fault: str | None, ops: int = 1500) -> tuple[int, int]:
    """(wrong replies, bad rows) of ``ops`` operations against a store."""
    store = Store(fault)
    traffic = traffic_mod.Traffic({
        "params": {"grain": "ChirperAccount", "publish_proportion": 0.3,
                   "read_n": 10, "warm_ops": 1},
        "config": {"grains": [{"class": "ChirperAccount",
                               "dense": ACCOUNTS}],
                   "graph": {"degree_table": TABLE}, "data_seed": 5},
        "grains": {"ChirperAccount": object()}, "reference": ref_mod,
        "child": 0, "n_children": 1, "callers": [0, 1, 2, 3],
        "n_callers": 4, "seed": 11, "fault": None,
        "response_timeout": 5.0})

    async def drive() -> int:
        wrong = 0
        for i in range(ops):
            ok, failed, bad = await traffic._operation(store, i % 4)
            assert not failed
            wrong += bad
        return wrong

    wrong = asyncio.run(drive())
    store.settle()
    keys, states, excluded = traffic.states()
    assert not excluded
    want = ref_mod.derive(states, np.array(keys))
    bad_rows = 0
    for i, k in enumerate(keys):
        row = store.ref.row(k)
        bad_rows += any(not np.array_equal(row[f], want[f][i])
                        for f in ref_mod.FIELDS)
    return wrong, bad_rows


def test_an_honest_store_passes():
    assert _judge(None) == (0, 0)


def test_a_store_that_loses_a_delivery_is_found():
    wrong, bad_rows = _judge("lost")
    assert bad_rows > 0 and wrong > 0   # the totals, and a reader's floor


def test_a_store_that_applies_a_delivery_twice_is_found():
    wrong, bad_rows = _judge("twice")
    assert bad_rows > 0 and wrong > 0   # the totals, and a repeat on a reply


def test_a_store_that_acknowledges_before_it_applies_is_found():
    wrong, bad_rows = _judge("early")
    assert wrong > 0 and bad_rows == 0  # only a read after the ack sees it
