"""By-hand tests of the per-layer metrics that read the program's stage
spans (``pytest chipbench/tests``): the three readers they brought against
hand-made ``ctx``, and a traced CPU rehearsal whose last line carries every
one of them. Nothing it prints is a measurement.
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

from loadgen import load_by_name  # noqa: E402
from test_chipbench import manifest, run_cell  # noqa: E402

ADDED = sorted(
    "wire.decode_us_per_msg wire.encode_us_per_msg gateway.pump_ms "
    "engine.deferrals_per_tick engine.worker_queue_ms engine.fence_wait_ms "
    "staging.fill_ms staging.transfer_ms tick.dispatch_ms tick.sync_ms "
    "engine.complete_hop_ms engine.resolve_ms writebehind.flush_ms "
    "writebehind.gather_ms writebehind.write_ms writebehind.rows_per_flush "
    "writebehind.loop_share_pct writebehind.compiles_in_window "
    "tick.compiles_in_window recovery.share_pct "
    "trace.unattributed_idle_pct silo.compiles_outside_stages "
    "gateway.msgs_per_read gateway.pump_loop_share_pct "
    "engine.resolve_loop_share_pct egress.flush_ms "
    "egress.flush_loop_share_pct".split())


def ctx(**over) -> dict:
    base = {"seconds": 20.0, "counters": {}, "histograms": {
        "flush.seconds": {"count": 40, "sum": 11.0},
        "ingest.tick.dispatch.seconds": {"count": 400, "sum": 0.4},
        "compile.flush.gather.seconds": {"count": 27, "sum": 0.2},
        "compile.flush.locate.seconds": {"count": 0, "sum": 0.0},
        "compile.ingest.tick.dispatch.seconds": {"count": 2, "sum": 5.0},
        "compile.engine.claim.seconds": {"count": 1, "sum": 0.1},
        "compile.other.seconds": {"count": 4, "sum": 0.1}},
        "trace": {"window_s": 3.0, "busy_s": 0.5, "idle_gaps": [
            ["otpu:flush.write", 1.0], ["unattributed", 0.25]]}}
    return {**base, **over}


def test_histogram_share_is_seconds_inside_over_seconds_of_window():
    read = load_by_name("readers", "histogram_share").read
    assert read(ctx(), stat="flush.seconds", scale=100) == \
        pytest.approx(55.0)
    assert read(ctx(), stat="no.such.seconds") is None


def test_histogram_count_sums_a_family_and_needs_proof_of_the_substrate():
    read = load_by_name("readers", "histogram_count").read
    proof = "ingest.tick.dispatch.seconds"
    assert read(ctx(), prefixes=["compile.flush."], present=proof) == 27
    assert read(ctx(), prefixes=["compile.ingest.", "compile.engine."],
                present=proof) == 3
    # a family nobody compiled in reads 0 where the program has the spans
    assert read(ctx(), prefixes=["compile.recover."], present=proof) == 0
    # per unit of a counter, for a share
    assert read(ctx(counters={"ingest.messages": 900}),
                prefixes=["compile.flush.gather"], present=proof,
                per_counter="ingest.messages", scale=100) == 3.0
    assert read(ctx(), prefixes=["compile.flush."], present=proof,
                per_counter="ingest.messages") is None
    # and nothing where it has not (the parent commit)
    assert read(ctx(histograms={}), prefixes=["compile.flush."],
                present=proof) is None


def test_idle_gap_share_is_a_label_over_the_idle_seconds():
    read = load_by_name("readers", "idle_gap_share").read
    assert read(ctx(), label="unattributed") == 10.0
    assert read(ctx(), label="otpu:flush.write") == 40.0
    assert read(ctx(), label="below the tenth") == 0.0
    assert read(ctx(trace=None), label="unattributed") is None
    assert read(ctx(trace={"window_s": 1.0, "busy_s": 1.0,
                           "idle_gaps": []}), label="unattributed") is None


def test_every_added_metric_is_a_file_and_an_entry():
    entries = {m["name"]: m for m in manifest()["per_layer"]}
    for name in ADDED:
        with open(os.path.join(BENCH, "layer_metrics", f"{name}.json")) as f:
            m = json.load(f)
        e = entries[name]
        assert {k: m[k] for k in ("name", "unit", "better", "source",
                                  "layer", "moves")} == \
            {k: e[k] for k in e if k != "workloads"}
        assert m["cells"] == e.get("workloads", "all")
        assert os.path.isfile(os.path.join(BENCH, "readers",
                                           m["reader"] + ".py"))


@pytest.fixture(scope="module")
def traced_line():
    rc, last, _ = run_cell("presence_heartbeat", trace=1, seconds=3.0)
    assert rc == 0 and last["correct"] is True
    return last


@pytest.mark.parametrize("name", ADDED)
def test_traced_rehearsal_finds_the_metric_in_the_last_line(traced_line,
                                                            name):
    v = traced_line["metrics"][name]
    assert v["value"] >= 0 and v["unit"]


def test_traced_rehearsal_tiles_the_old_spans_and_names_the_gaps(
        traced_line):
    m = {k: v["value"] for k, v in traced_line["metrics"].items()}
    # the new spans tile the old ones: same clock reads, so within rounding
    assert m["tick.dispatch_ms"] + m["tick.sync_ms"] <= m["tick.wall_ms"]
    assert m["tick.dispatch_ms"] + m["tick.sync_ms"] >= \
        0.95 * m["tick.wall_ms"]
    # who compiled: the program's books against the harness's meter, up
    # to one flush's nine gather programs at each edge of the window (the
    # loop reads its registry a little after the meter's instants)
    booked = m["writebehind.compiles_in_window"] + \
        m["tick.compiles_in_window"] + m["silo.compiles_outside_stages"]
    assert abs(booked - m["kernels.compiles_in_window"]) <= 18
    assert any(name.startswith("otpu:")
               for name, _s in traced_line["breakdown"]["idle_gaps"])
