"""By-hand tests of the hot and the cold requests (``pytest
chipbench/tests``): the hot set of ``ycsb_ops`` follows from the data files
alone; on synthetic records the clients' numbers count every request in
every end-to-end metric and report the two populations apart; a wrong reply
or a failed call on a hot record leaves no count and no comparison; the
reader of ``ctx["clients"]``; which end-to-end metrics a cell reports, by
its own file.
Nothing here is a measurement.
"""

import asyncio
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as bench  # noqa: E402
from loadgen import Records, drive, load_by_name  # noqa: E402


def load(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


# -- the hot set ---------------------------------------------------------

def ycsb_traffic(child: int, seed: int, **params):
    wl = load("workloads", "ycsb_a_zipf")
    cfg = load("configs", wl["config"])
    n = wl["client_procs"]
    return load_by_name("traffic", wl["generator"]).Traffic({
        "config": cfg, "workload": wl, "params": {**wl["params"], **params},
        "grains": {wl["params"]["grain"]: object},
        "reference": load_by_name("references", cfg["reference"]),
        "child": child, "n_children": n, "n_callers": wl["callers"],
        "callers": list(range(child, wl["callers"], n))[:2], "seed": seed,
        "response_timeout": wl["response_timeout"]})


@pytest.fixture(scope="module")
def key_mass():
    y = load_by_name("traffic", "ycsb_ops")
    cfg = load("configs", "ycsb-1kb")
    return y.ScrambledZipfian(cfg["recordcount"], 0.99).key_mass()


def test_the_hot_set_is_the_records_with_an_operation_in_flight(key_mass):
    t = ycsb_traffic(0, 1)
    hot = np.flatnonzero(key_mass * 1024 >= 1.0)
    assert np.array_equal(np.flatnonzero(t.hot), hot)
    # the 40 most probable records, 17.1 % of the draws (PERF.md §4)
    assert len(hot) == 40
    assert set(hot) == set(np.argsort(-key_mass)[:40])
    assert key_mass[hot].sum() == pytest.approx(0.17147, abs=1e-5)
    assert t.about() == {"hot_records": 40, "hot_mass_pct": pytest.approx(
        100.0 * key_mass[hot].sum())}


@pytest.mark.parametrize("child,seed", [(0, 2), (1, 1), (2, 1), (3, 77)])
def test_every_child_and_every_seed_has_the_same_hot_set(child, seed):
    a, b = ycsb_traffic(0, 1), ycsb_traffic(child, seed)
    assert np.array_equal(a.hot, b.hot) and a.about() == b.about()
    # and a child's draws land on hot records of its own about as often
    # as the source's mass says (the deal gives each child a quarter)
    ops = b._draw(b.rngs[0])
    share = np.mean([b.hot[k] for k, *_ in ops])
    assert abs(share - b.hot_mass) < 0.05
    assert b.hot_of(0) == b.hot_of(1) == 0      # nothing in flight yet


def test_a_lower_threshold_widens_the_set_and_none_marks_nothing():
    t = ycsb_traffic(0, 1, hot_in_flight=0.5)
    assert t.about()["hot_records"] == 80
    assert t.hot_mass == pytest.approx(0.19852, abs=1e-5)
    wl = load("workloads", "ycsb_a_zipf")
    del wl["params"]["hot_in_flight"]       # an older cell's file
    cfg = load("configs", wl["config"])
    t = load_by_name("traffic", wl["generator"]).Traffic({
        "config": cfg, "workload": wl, "params": wl["params"],
        "grains": {wl["params"]["grain"]: object},
        "reference": load_by_name("references", cfg["reference"]),
        "child": 0, "n_children": 4, "n_callers": 1024, "callers": [0],
        "seed": 1, "response_timeout": 1.0})
    assert t.about() == {"hot_records": 0, "hot_mass_pct": 0.0}


# -- the clients' numbers on synthetic records ----------------------------

T0, SECONDS = 100.0, 20.0


def records(rows: list) -> dict:
    """(done - t0, latency s, ok, failed, wrong, hot) -> merged columns."""
    rec = Records(4)
    for at, lat, ok, failed, wrong, hot in rows:
        rec.add(T0 + at - lat, T0 + at - lat, T0 + at, ok, failed, wrong, hot)
    return rec.columns()


def planted(n_cold: int = 9000, n_hot: int = 1000) -> list:
    """Cold requests of 20-30 ms, hot ones of 1-2 s: 10 % of the window."""
    rng = np.random.default_rng(35)
    cold = [(at, lat, 1, 0, 0, 0) for at, lat in zip(
        rng.uniform(3, 19, n_cold), rng.uniform(0.020, 0.030, n_cold))]
    hot = [(at, lat, 1, 0, 0, 1) for at, lat in zip(
        rng.uniform(3, 19, n_hot), rng.uniform(1.0, 2.0, n_hot))]
    return cold + hot


def test_a_slow_hot_mode_is_in_every_end_to_end_number_and_apart_beside():
    c = bench.client_numbers(records(planted()), T0, SECONDS)
    assert c["requests_in_window"] == 10000 and c["ok_calls"] == 10000
    assert c["hot_requests"] == 1000 and c["hot_share_pct"] == 10.0
    assert c["latency_samples"] == 10000
    # over all requests: the median is cold, the 95th is a parked caller
    assert 20 < c["latency_ms"]["50"] < 30
    assert 1000 < c["latency_ms"]["95"] < 2000
    # the populations apart: the cold tail does not see the hot mode
    assert 20 < c["latency_ms_cold"]["50"] < c["latency_ms_cold"]["95"] < 30
    assert 1000 < c["latency_ms_hot"]["50"] < 2000
    # and the median over all is not the cold median: hot requests count
    assert c["latency_ms"]["50"] > c["latency_ms_cold"]["50"]


def test_requests_outside_the_window_count_nowhere():
    rows = planted(100, 10) + [(-0.5, 0.02, 1, 0, 0, 0), (20.5, 3.0, 1, 0, 0,
                                                          1)]
    c = bench.client_numbers(records(rows), T0, SECONDS)
    assert c["requests_in_window"] == 110 and c["hot_requests"] == 10
    assert c["requests_in_flight_at_end"] == 2


def test_records_without_a_hot_request_read_the_same_both_ways():
    rows = [r[:5] + (0,) for r in planted()]
    c = bench.client_numbers(records(rows), T0, SECONDS)
    assert c["hot_requests"] == 0 and c["hot_share_pct"] == 0.0
    assert c["latency_ms_cold"] == {q: c["latency_ms"][q]
                                    for q in bench.QUANTILES}
    assert set(c["latency_ms_hot"].values()) == {None}


class FakeClient:
    """Answers every call with what the plain reference owes."""

    def __init__(self, ref) -> None:
        self.ref = ref

    def call_batch(self, cls, method, calls, timeout=None):
        async def one(key, kw):
            return getattr(self.ref, method)(key, **kw)
        return [one(k, kw) for k, kw in calls]

    def get_grain(self, cls, key):
        ref = self.ref

        class Grain:
            async def ping(self, x):
                return ref.ping(key, x)
        return Grain()


MARK_NOTHING = sorted(
    w[:-5] for w in os.listdir(os.path.join(BENCH, "workloads"))
    if "hot_in_flight" not in load("workloads", w[:-5])["params"])


@pytest.mark.parametrize("cell", MARK_NOTHING)
def test_a_kind_that_marks_nothing_reports_no_hot_request(cell):
    """The kinds that draw without replacement or uniformly have no
    ``hot_of``: the load generator books every request of theirs as cold,
    so their cells read the same p95 over all requests and over the cold
    ones."""
    wl = load("workloads", cell)
    wl = bench.merged(wl, wl.pop("rehearse", {}))
    cfg = load("configs", wl["config"])
    cfg = bench.merged(cfg, cfg.pop("rehearse", {}))
    ref_mod = load_by_name("references", cfg["reference"])
    t = load_by_name("traffic", wl["generator"]).Traffic({
        "config": cfg, "workload": wl, "params": wl["params"],
        "grains": {wl["params"]["grain"]: object}, "reference": ref_mod,
        "child": 0, "n_children": 1, "n_callers": wl["callers"],
        "callers": list(range(wl["callers"])), "seed": 35,
        "response_timeout": 1.0})
    assert not hasattr(t, "about") and not hasattr(t, "hot_of")

    class Few:
        """The kind, driven through four of its callers."""
        n_callers, calls_per_request = 4, t.calls_per_request
        request = staticmethod(t.request)

    async def go():
        t0 = time.monotonic() + 0.02
        rec, _ = await drive(Few, FakeClient(ref_mod.Reference()), "closed",
                             t0, 0.2, rate=None, seed=35)
        return t0, rec.columns()
    t0, cols = asyncio.run(go())
    assert len(cols["hot"]) > 8 and not cols["hot"].any()
    assert (cols["ok"] == t.calls_per_request).all()
    assert not cols["failed"].any() and not cols["wrong"].any()
    c = bench.client_numbers(cols, t0, 0.2)
    assert c["requests_in_window"] > 4 and c["hot_requests"] == 0
    assert c["latency_ms"]["95"] == c["latency_ms_cold"]["95"]


# -- a hot request leaves the tail, never the comparison --------------------

ROWS_OK = {"bad_rows": 0}
STORED_OK = {"not_readable": 0}


def decide(rows: list) -> tuple[dict, bool, dict]:
    cols = records(rows)
    c = bench.client_numbers(cols, T0, SECONDS)
    correct, compared = bench.verdict(cols, 0, c["ok_calls"], ROWS_OK,
                                      STORED_OK, 0)
    return c, correct, compared


def test_a_wrong_reply_on_a_hot_record_turns_correct_false():
    c, correct, compared = decide(planted(100, 10) + [(5.0, 1.5, 0, 0, 1, 1)])
    assert c["wrong_calls"] == 1 and c["hot_requests"] == 11
    assert c["latency_samples"] == 110      # a wrong reply is no sample
    assert correct is False
    assert compared["wrong_replies"] == {"value": 1, "limit": 0}


def test_a_failed_call_on_a_hot_record_is_counted_as_failed():
    c, correct, _ = decide(planted(100, 10) + [(5.0, 30.0, 0, 1, 0, 1)])
    assert c["failed_calls"] == 1 and c["ok_calls"] == 110
    assert c["latency_samples"] == 110
    assert correct is True   # a timeout is a failure, not a wrong answer


def test_a_clean_window_is_correct_and_each_number_has_its_limit():
    _c, correct, compared = decide(planted(100, 10))
    assert correct is True
    assert all(v == {"value": 0, "limit": 0} for v in compared.values())
    cols = records(planted(10, 1))
    for broken in ({"rows": {"bad_rows": 2}}, {"stored": {"not_readable": 1}},
                   {"grown": 8}, {"ok": 0}, {"warm": 1}):
        ok, compared = bench.verdict(
            cols, broken.get("warm", 0), broken.get("ok", 11),
            broken.get("rows", ROWS_OK), broken.get("stored", STORED_OK),
            broken.get("grown", 0))
        assert ok is False
        assert sum(v["value"] > v["limit"] for v in compared.values()) == 1


# -- the reader and the manifest ---------------------------------------------

def test_the_reader_finds_a_number_by_its_path():
    read = load_by_name("readers", "clients_value").read
    ctx = {"clients": bench.client_numbers(records(planted()), T0, SECONDS)}
    assert read(ctx, path=["hot_share_pct"]) == 10.0
    assert 1000 < read(ctx, path=["latency_ms_hot", "50"]) < 2000
    assert 20 < read(ctx, path=["latency_ms_cold", "95"]) < 30
    assert read(ctx, path=["latency_ms_cold", "95"], scale=1e-3) < 0.03
    # an empty population, an unknown key, an older harness: nothing to read
    none_hot = {"clients": bench.client_numbers(
        records([r[:5] + (0,) for r in planted(50, 5)]), T0, SECONDS)}
    assert read(none_hot, path=["latency_ms_hot", "50"]) is None
    assert read(ctx, path=["no_such", "50"]) is None
    assert read({}, path=["hot_share_pct"]) is None


def manifest() -> dict:
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_new_metrics_are_files_entries_and_read_what_the_line_prints():
    entries = {e["name"]: e for e in manifest()["per_layer"]}
    ctx = {"clients": bench.client_numbers(records(planted()), T0, SECONDS)}
    want = {"client.hot_latency_p90_ms":
                ctx["clients"]["latency_ms_hot"]["90"],
            "client.cold_latency_p95_ms":
                ctx["clients"]["latency_ms_cold"]["95"]}
    for name, value in want.items():
        d = load("layer_metrics", name)
        assert entries[name]["workloads"] == d["cells"] == ["ycsb_a_zipf"]
        assert entries[name]["layer"] == "client (load generator)"
        got = load_by_name("readers", d["reader"]).read(ctx, **d["args"])
        assert got == value
    gone = {"recovery.first_touch_ms", "recovery.first_touch_share_pct"}
    assert not gone & set(entries)
    assert not gone & {f[:-5] for f in os.listdir(
        os.path.join(BENCH, "layer_metrics"))}


def test_a_cell_reports_every_end_to_end_metric_its_own_file_does_not_leave_out():
    """The harness reads no manifest: a new cell reports all four metrics,
    and the manifest's lists (the only form the driver reads) say the same
    as the cells' files."""
    m = manifest()
    left_out = {w["name"]: set(load("workloads", w["name"]).get(
        "end_to_end_left_out", ())) for w in m["workloads"]}
    assert left_out == {"ycsb_a_zipf": {"latency_p95_ms"},
                        "presence_heartbeat": set(), "presence_4chip": set()}
    for e in m["end_to_end"]:
        reporting = [c for c, out in left_out.items() if e["name"] not in out]
        assert e.get("workloads", list(left_out)) == reporting, e
    for out in left_out.values():
        assert "setup_s" not in out and len(m["end_to_end"]) - len(out) >= 2
    # a per-layer metric moves a metric that each of its cells reports
    for e in m["per_layer"]:
        for cell in e.get("workloads", list(left_out)):
            assert e["moves"] not in left_out[cell], e
    assert "BENCHMARK.json" not in open(os.path.join(BENCH, "run.py")).read()
