"""Tail-based trace retention + streaming OTLP export (observability
tracing/export, ISSUE 5): slow/errored traces survive the tail decision
while fast-clean ones drop; straggler legs inside the quiescence window
join; cross-silo legs pull over the real control path when a silo retains
a trace; OtlpSink batching/retry/drop against a local fake collector;
rejection/resend span events; the response-leg network span; and the
sampled-trace hot lane rolling the head die inside the lane."""

import asyncio
import time

import pytest

from orleans_tpu.core.message import RejectionType, make_rejection
from orleans_tpu.management import ManagementGrain
from orleans_tpu.observability.export import OtlpSink, spans_to_otlp
from orleans_tpu.observability.tracing import (
    LatencyErrorPolicy,
    SpanCollector,
)
from orleans_tpu.runtime import Grain
from orleans_tpu.runtime.runtime_client import RuntimeClient
from orleans_tpu.testing import TestClusterBuilder


class EchoGrain(Grain):
    async def ping(self, x: int) -> int:
        return x


class SlowGrain(Grain):
    async def nap(self) -> str:
        await asyncio.sleep(0.12)
        return "slept"


class FailGrain(Grain):
    async def boom(self) -> None:
        raise ValueError("injected failure")


class SlowEchoGrain(Grain):
    async def ping(self, x: int) -> int:
        await asyncio.sleep(0.1)
        return x


class ProxyGrain(Grain):
    async def relay(self, key: int, x: int) -> int:
        return await self.get_grain(SlowEchoGrain, key).ping(x)


# ----------------------------------------------------------------------
# Tentpole acceptance: slow + errored survive the tail, fast-clean drops
# ----------------------------------------------------------------------
async def test_tail_keeps_slow_and_errored_drops_fast_clean():
    """ISSUE 5 acceptance: tail mode, head rate 1.0-record/0-keep — the
    injected slow and failing requests export with ALL legs while >=95%
    of fast-clean traces drop, and kept/dropped counts are visible via
    the ManagementGrain."""
    n_fast = 60
    cluster = (TestClusterBuilder(1)
               .add_grains(EchoGrain, SlowGrain, FailGrain)
               .with_tracing(tail=True, tail_window=0.15,
                             slow_threshold=0.05, leg_ttl=0.5)
               .build())
    async with cluster:
        assert await cluster.grain(SlowGrain, 1).nap() == "slept"
        with pytest.raises(ValueError):
            await cluster.grain(FailGrain, 2).boom()
        for i in range(n_fast):
            assert await cluster.grain(EchoGrain, i % 8).ping(i) == i

        ct = cluster.client.tracer
        # nothing committed yet: the decision waits for the tail
        assert ct.retention_stats()["tail"] is True
        await cluster.drain_traces()

        spans = ct.snapshot()
        names = {s["name"] for s in spans}
        assert "SlowGrain.nap" in names and "FailGrain.boom" in names
        # all legs retained, including the silo-side server turns (pulled
        # off the silo collector at retention time) and network legs
        kept_tids = {s["trace_id"] for s in spans}
        assert len(kept_tids) == 2
        for tid in kept_tids:
            kinds = {s["kind"] for s in spans if s["trace_id"] == tid}
            assert {"client", "server", "network"} <= kinds
            silos = {s["silo"] for s in spans if s["trace_id"] == tid}
            assert "silo0" in silos and "client" in silos
        # the errored trace carries the error attr; the slow one the
        # retention reason
        reasons = {s["attrs"].get("retained") for s in spans
                   if s["parent_id"] is None}
        assert reasons == {"slow", "error"}

        st = ct.retention_stats()
        assert st["kept"] == 2
        assert st["dropped"] >= n_fast * 0.95

        # cluster-wide counters through the management surface: the two
        # retained traces were PULLED off the silo (kept there too), the
        # fast-clean legs expired un-pulled (dropped there)
        mgmt = cluster.grain(ManagementGrain, 0)
        stats = await mgmt.get_retention_stats()
        totals = stats["totals"]
        assert totals["kept"] >= 2 and totals["pulled"] >= 2
        assert totals["dropped"] >= n_fast * 0.95
        assert len(stats["per_silo"]) == 1


async def test_tail_forced_retention_survives_policy_drop():
    cluster = (TestClusterBuilder(1).add_grains(EchoGrain)
               .with_tracing(tail=True, tail_window=0.1,
                             slow_threshold=10.0, leg_ttl=0.4)
               .build())
    async with cluster:
        assert await cluster.grain(EchoGrain, 1).ping(1) == 1
        ct = cluster.client.tracer
        tid = next(iter(ct.pending))
        ct.force_retain(tid)
        assert await cluster.grain(EchoGrain, 1).ping(2) == 2
        await cluster.drain_traces()
        st = ct.retention_stats()
        assert st["kept"] == 1 and st["dropped"] >= 1
        roots = [s for s in ct.snapshot() if s["parent_id"] is None]
        assert len(roots) == 1 and roots[0]["attrs"]["retained"] == "forced"


# ----------------------------------------------------------------------
# Straggler legs + quiescence window (collector-level, loop-less)
# ----------------------------------------------------------------------
def test_straggler_leg_within_quiescence_window_included():
    c = SpanCollector("s", tail=True, tail_window=0.05,
                      policy=LatencyErrorPolicy(slow_threshold=0.01))
    root = c.open("op", "client", trace_id=7, parent_id=None)
    c.close(root, duration=0.5)          # slow: will be retained
    # straggler (e.g. the response-leg network span) lands AFTER the root
    # closed but inside the window — it must ride along
    c.record(7, root.span_id, "network", "network", time.time(), 0.001,
             leg="response")
    c.flush_tail()                       # window not elapsed: no decision
    assert c.retention_stats()["kept"] == 0 and len(c.pending) == 1
    time.sleep(0.06)
    c.flush_tail()                       # quiesced now: decide
    st = c.retention_stats()
    assert st["kept"] == 1 and st["buffered"] == 0
    got = c.snapshot(trace_id=7)
    assert {s["kind"] for s in got} == {"client", "network"}

    # a leg arriving after the decision starts a leg-only entry that can
    # only expire (its trace was already decided elsewhere)
    c.record(7, root.span_id, "network", "network", time.time(), 0.001)
    c.flush_tail(force=True)
    assert c.retention_stats()["dropped"] == 1


def test_device_tick_trace_bypasses_tail_stage():
    """The synthetic device-tick trace (endless parent-less spans on one
    shared trace_id) must land straight in the bounded ring even in tail
    mode — buffering it would re-arm the quiescence window forever and
    grow one pending entry without bound."""
    c = SpanCollector("s", tail=True, tail_window=10.0)
    for i in range(50):
        c.record(c.device_trace_id, None, f"tick{i}", "device_tick",
                 time.time(), 0.001, batch=1)
    assert len(c.pending) == 0
    assert len(c.spans) == 50
    assert c.retention_stats()["kept"] == 0  # telemetry, not retention


def test_pull_leaves_locally_rooted_pending_trace_for_its_own_decision():
    """An operator peeking at a live trace id (ctl_trace_spans in tail
    mode) must not steal a HERE-rooted trace from its own tail decision
    and sink export — only leg-only entries promote on pull."""
    c = SpanCollector("s", tail=True, tail_window=0.02,
                      policy=LatencyErrorPolicy(slow_threshold=0.01))
    root = c.open("op", "client", trace_id=9, parent_id=None)
    c.close(root, duration=0.5)
    got = c.pull(9)
    assert len(got) == 1                       # read-only view
    assert 9 in c.pending                      # still owns its decision
    assert c.retention_stats()["pulled"] == 0
    time.sleep(0.03)
    c.flush_tail()
    assert c.retention_stats()["kept"] == 1    # normal retention ran


def test_tail_pending_buffer_is_bounded():
    c = SpanCollector("s", tail=True, max_pending=8)
    for i in range(20):
        c.close(c.open(f"op{i}", "server", trace_id=1000 + i,
                       parent_id=1))    # leg-only: never decided
    assert len(c.pending) == 8
    assert c.retention_stats()["dropped"] == 12  # evicted oldest


def test_latency_policy_percentile_mode():
    pol = LatencyErrorPolicy(slow_threshold=0.0, slow_percentile=0.9)
    c = SpanCollector("s", tail=True, tail_window=0.0, policy=pol)

    def one(dur):
        root = c.open("op", "client", trace_id=c.new_trace_id(),
                      parent_id=None)
        c.close(root, duration=dur)
        c.flush_tail(force=True)

    for _ in range(30):
        one(0.001)                      # build history: all fast
    kept_before = c.retention_stats()["kept"]
    one(1.0)                            # way past p90 of history
    assert c.retention_stats()["kept"] == kept_before + 1


# ----------------------------------------------------------------------
# Cross-silo leg pull over the REAL control path (silo-rooted trace)
# ----------------------------------------------------------------------
async def test_cross_silo_leg_pull_via_control_path():
    """Client untraced -> the relay silo roots the trace for its outgoing
    call; the callee runs on the OTHER silo; retention at the rooting silo
    pulls the remote server leg via ctl_trace_spans (SYSTEM RPC), which
    also promotes/counts it kept on the remote side."""
    cluster = (TestClusterBuilder(2).add_grains(ProxyGrain, SlowEchoGrain)
               .with_tracing(tail=True, tail_window=0.15,
                             slow_threshold=0.05, leg_ttl=1.0,
                             client=False)
               .build())
    async with cluster:
        assert cluster.client.tracer is None  # traces must root silo-side
        pair = None
        for key in range(16):
            assert await cluster.grain(ProxyGrain, key).relay(key, 5) == 5
            proxy_gid = cluster.grain(ProxyGrain, key).grain_id
            echo_gid = cluster.grain(SlowEchoGrain, key).grain_id
            hosts = {}
            for s in cluster.silos:
                if s.catalog.by_grain.get(proxy_gid):
                    hosts["proxy"] = s
                if s.catalog.by_grain.get(echo_gid):
                    hosts["echo"] = s
            if len(hosts) == 2 and hosts["proxy"] is not hosts["echo"]:
                pair = (hosts["proxy"], hosts["echo"])
                break
        assert pair is not None, "no cross-silo placement in 16 keys"
        rooter, remote = pair

        await cluster.drain_traces()
        # the rooting silo retained the slow trace WITH the remote leg
        retained = rooter.tracer.snapshot()
        assert any(s["parent_id"] is None
                   and s["attrs"].get("retained") == "slow"
                   and s["name"] == "SlowEchoGrain.ping"
                   for s in retained), retained
        remote_legs = [s for s in retained
                       if s["silo"] == remote.config.name
                       and s["kind"] == "server"]
        assert remote_legs, "remote server leg was not pulled"
        # the pull handed the legs off (counted kept, not expired)...
        assert remote.tracer.retention_stats()["pulled"] >= 1
        # ...without double-storing them: exactly one collector (the
        # puller) holds a pulled trace, so cluster-wide merges
        # (get_trace_spans / export_trace) never count a leg twice
        pulled_tids = {s["trace_id"] for s in remote_legs}
        assert not [s for s in remote.tracer.snapshot()
                    if s["trace_id"] in pulled_tids]


async def test_pull_dedups_span_ids_across_fanout(monkeypatch):
    """Cross-process span-level dedup (ISSUE 18 satellite): worker-process
    silos make duplicate pulls real — a forwarded leg (or a span a peer
    itself pulled and retained) can come back from MORE THAN ONE silo in
    the ctl_trace_spans fan-out, and export must not double-count it.
    The retained-trace pull keeps the first copy of each span_id."""
    from orleans_tpu.core.ids import SiloAddress
    from orleans_tpu.runtime import SiloBuilder

    silo = (SiloBuilder().with_name("dedup")
            .with_config(trace_enabled=True, trace_tail_enabled=True)
            .build())
    a1 = SiloAddress("127.0.0.1", 11, 1)
    a2 = SiloAddress("127.0.0.1", 22, 1)
    silo.locator.alive_list = [silo.silo_address, a1, a2]

    def leg(sid):
        return {"trace_id": 7, "span_id": sid, "parent_id": None,
                "name": f"op{sid}", "kind": "server", "silo": "w",
                "start": 0.0, "duration": 0.1, "attrs": {}}

    async def fake_send_request(**kw):
        # peer 1 and peer 2 both hold span 101 (one forwarded its leg
        # through the other); 102 lacks a span_id and must pass through
        if kw["target_silo"] == a1:
            return [leg(100), leg(101)]
        return [leg(101), leg(103), {"trace_id": 7, "attrs": {}}]

    monkeypatch.setattr(silo.runtime_client, "send_request",
                        fake_send_request)
    out = await silo._pull_trace_legs(7)
    assert [d.get("span_id") for d in out] == [100, 101, 103, None]


# ----------------------------------------------------------------------
# OTLP sink: batching / payload shape / retry / drop
# ----------------------------------------------------------------------
from fake_otlp import FakeCollector as _FakeCollector  # noqa: E402


def _mk_span_dicts(n, trace_id=0xabc, error_on=None, events_on=None):
    out = []
    for i in range(n):
        d = {"trace_id": trace_id, "span_id": 100 + i,
             "parent_id": 99 if i else None, "name": f"op{i}",
             "kind": "server" if i else "client", "silo": "silo0",
             "start": 1000.0 + i, "duration": 0.25, "attrs": {"n": i}}
        if error_on is not None and i == error_on:
            d["attrs"]["error"] = "ValueError"
        if events_on is not None and i == events_on:
            d["events"] = [["resend", 1000.5, {"rejection": "TRANSIENT"}]]
        out.append(d)
    return out


def test_otlp_payload_shape():
    payload = spans_to_otlp(_mk_span_dicts(2, error_on=1, events_on=1),
                            service_name="svc")
    rs = payload["resourceSpans"][0]
    res_attrs = {a["key"]: a["value"] for a in rs["resource"]["attributes"]}
    assert res_attrs["service.name"] == {"stringValue": "svc"}
    spans = rs["scopeSpans"][0]["spans"]
    assert len(spans) == 2
    root, child = spans
    assert len(root["traceId"]) == 32 and len(root["spanId"]) == 16
    assert "parentSpanId" not in root and len(child["parentSpanId"]) == 16
    assert root["kind"] == 3 and child["kind"] == 2  # CLIENT / SERVER
    assert int(child["endTimeUnixNano"]) - int(child["startTimeUnixNano"]) \
        == int(0.25 * 1e9)
    assert child["status"] == {"code": 2, "message": "ValueError"}
    assert child["events"][0]["name"] == "resend"
    span_attrs = {a["key"] for a in child["attributes"]}
    assert {"n", "orleans.silo", "orleans.kind"} <= span_attrs


async def test_otlp_sink_batches_to_local_collector():
    col = _FakeCollector()
    try:
        sink = OtlpSink(col.endpoint, batch_size=4, flush_interval=0.05)
        sink.offer(_mk_span_dicts(6))
        # offer kicked the background flusher (full batch) — settle on the
        # counters instead of racing it with an explicit flush
        for _ in range(200):
            if sink.stats()["exported"] >= 6:
                break
            await asyncio.sleep(0.01)
        assert col.span_count() == 6
        sizes = sorted(
            len(ss["spans"])
            for b in col.bodies for rs in b["resourceSpans"]
            for ss in rs["scopeSpans"])
        assert sizes == [2, 4]  # bounded batches, nothing lost
        st = sink.stats()
        assert st["exported"] == 6 and st["export_batches"] == 2
        assert st["export_dropped"] == 0
        await sink.aclose()
    finally:
        col.close()


async def test_otlp_sink_retries_transient_failure():
    col = _FakeCollector(fail_first=1)
    try:
        sink = OtlpSink(col.endpoint, batch_size=8, max_retries=2,
                        retry_backoff=0.01)
        sink.offer(_mk_span_dicts(3))
        await sink.flush()
        st = sink.stats()
        assert st["exported"] == 3 and st["export_dropped"] == 0
        assert st["export_retries"] >= 1
        await sink.aclose()
    finally:
        col.close()


async def test_otlp_sink_drops_and_counts_when_unreachable():
    # closed port: connection refused immediately, no real network
    sink = OtlpSink("http://127.0.0.1:9/v1/traces", batch_size=4,
                    max_retries=1, retry_backoff=0.01, timeout=0.2)
    sink.offer(_mk_span_dicts(5))
    await sink.flush()   # must not raise
    st = sink.stats()
    assert st["exported"] == 0 and st["export_dropped"] == 5
    await sink.aclose()


async def test_otlp_sink_queue_overflow_drops_oldest():
    sink = OtlpSink("http://127.0.0.1:9/v1/traces", max_queue=4)
    sink.offer(_mk_span_dicts(6))
    assert sink.stats()["queued"] == 4
    assert sink.stats()["export_dropped"] == 2
    await sink.aclose(flush=False)


async def test_tail_cluster_streams_retained_trace_to_collector():
    """End to end: tail cluster + OTLP endpoint — the retained slow trace
    (with its pulled silo legs) lands at the collector; dropped fast-clean
    traces never ship."""
    col = _FakeCollector()
    try:
        cluster = (TestClusterBuilder(1).add_grains(EchoGrain, SlowGrain)
                   .with_tracing(tail=True, tail_window=0.1,
                                 slow_threshold=0.05, leg_ttl=0.4,
                                 otlp_endpoint=col.endpoint)
                   .build())
        async with cluster:
            assert await cluster.grain(SlowGrain, 1).nap() == "slept"
            for i in range(10):
                assert await cluster.grain(EchoGrain, 1).ping(i) == i
            await cluster.drain_traces()
            shipped = [sp for b in col.bodies
                       for rs in b["resourceSpans"]
                       for ss in rs["scopeSpans"] for sp in ss["spans"]]
            names = {s["name"] for s in shipped}
            assert "SlowGrain.nap" in names
            assert not any("EchoGrain" in n for n in names)
            # the pulled silo leg shipped too (whole trace, one shipper)
            silos = {a["value"]["stringValue"] for s in shipped
                     for a in s["attributes"] if a["key"] == "orleans.silo"}
            assert "silo0" in silos
            st = cluster.client.tracer.retention_stats()
            assert st["exported"] == len(shipped) > 0
    finally:
        col.close()


# ----------------------------------------------------------------------
# Span events: rejections + transient resends (runtime_client side)
# ----------------------------------------------------------------------
class _LoopbackClient(RuntimeClient):
    """Captures transmits so tests can hand-deliver responses."""

    def __init__(self):
        super().__init__(response_timeout=5.0)
        self.sent = []

    @property
    def silo_address(self):
        return None

    def transmit(self, msg):
        self.sent.append(msg)


async def test_resend_and_rejected_events_attach_to_client_span():
    client = _LoopbackClient()
    tracer = client.enable_tracing(1.0)
    res = client.send_request(
        target_grain=None, grain_class=EchoGrain,
        interface_name="EchoGrain", method_name="ping",
        args=(1,), kwargs={})
    req = client.sent[-1]
    cb = client.callbacks[req.id]
    assert cb.span is not None

    # transient rejection: resend scheduled + "rejected"/"resend" events
    client.receive_response(
        make_rejection(req, RejectionType.TRANSIENT, "silo dying"))
    assert [e[0] for e in cb.span.events] == ["rejected", "resend"]
    assert cb.span.events[1][2]["rejection"] == "TRANSIENT"
    assert req.id in client.callbacks  # still outstanding (retrying)

    # exhaust the resend budget -> terminal rejection, span errored
    from orleans_tpu.runtime.runtime_client import MAX_RESEND_COUNT
    cb.message.resend_count = MAX_RESEND_COUNT
    client.receive_response(
        make_rejection(req, RejectionType.TRANSIENT, "still dying"))
    from orleans_tpu.core.errors import RejectionError
    with pytest.raises(RejectionError):
        await res
    spans = tracer.snapshot()
    root = [s for s in spans if s["kind"] == "client"][-1]
    assert root["attrs"]["error"] == "RejectionError"
    names = [e[0] for e in root["events"]]
    assert names.count("rejected") == 2 and "resend" in names


async def test_overload_rejection_records_event_span_server_side():
    class BusyGrain(Grain):
        async def work(self):
            await asyncio.sleep(0.2)
            return 1

    cluster = (TestClusterBuilder(1).add_grains(BusyGrain)
               .with_config(max_enqueued_requests=1)
               .with_tracing().build())
    async with cluster:
        g = cluster.grain(BusyGrain, 1)
        results = await asyncio.gather(*(g.work() for _ in range(5)),
                                       return_exceptions=True)
        assert any(isinstance(r, Exception) for r in results)
        assert any(r == 1 for r in results)
        # the silo annotated the overload rejection under the caller's
        # invoke span; the client's span carries the rejected event
        silo_events = [s for s in cluster.silos[0].tracer.snapshot()
                       if s["kind"] == "event" and s["name"] == "reject"]
        assert silo_events and \
            silo_events[0]["attrs"]["type"] == "OVERLOADED"
        client_roots = [s for s in cluster.client.tracer.snapshot()
                        if s["kind"] == "client" and s.get("events")]
        assert any(e[0] == "rejected" for s in client_roots
                   for e in s["events"])


# ----------------------------------------------------------------------
# Response-leg network span
# ----------------------------------------------------------------------
async def test_response_leg_network_span_recorded():
    cluster = (TestClusterBuilder(1).add_grains(EchoGrain)
               .with_tracing().build())
    async with cluster:
        assert await cluster.grain(EchoGrain, 1).ping(7) == 7
        spans = cluster.trace_spans()
        nets = [s for s in spans if s["kind"] == "network"]
        legs = [s for s in nets if s["attrs"].get("leg") == "response"]
        assert legs, f"no response-leg network span in {nets}"
        # recorded on the RECEIVING side (the client observed arrival),
        # parented under the server turn span that stamped it
        assert legs[-1]["silo"] == "client"
        server_ids = {s["span_id"] for s in spans if s["kind"] == "server"}
        assert legs[-1]["parent_id"] in server_ids


# ----------------------------------------------------------------------
# Sampled-trace hot lane: the lane rolls the die itself
# ----------------------------------------------------------------------
async def test_hotlane_serves_unsampled_majority_at_low_rate():
    cluster = (TestClusterBuilder(1).add_grains(EchoGrain)
               .with_tracing(sample_rate=0.01).build())
    async with cluster:
        g = cluster.grain(EchoGrain, 1)
        assert await g.ping(0) == 0    # activate (always messaging)
        client = cluster.client
        h0, f0 = client.hot_hits, client.hot_fallbacks
        n = 300
        for i in range(n):
            assert await g.ping(i) == i
        hits = client.hot_hits - h0
        falls = client.hot_fallbacks - f0
        assert hits + falls == n
        # binomial(300, 0.99): the lane must keep the unsampled majority
        assert hits >= n * 0.8, (hits, falls)
        # every fallback IS a sampled call: exactly that many root client
        # spans were recorded (the roll is handed over, never re-rolled)
        roots = [s for s in client.tracer.snapshot()
                 if s["kind"] == "client" and s["parent_id"] is None]
        assert len(roots) == falls


async def test_hotlane_rate_zero_and_one_unchanged():
    for rate, expect_hot in ((0.0, True), (1.0, False)):
        cluster = (TestClusterBuilder(1).add_grains(EchoGrain)
                   .with_tracing(sample_rate=rate).build())
        async with cluster:
            g = cluster.grain(EchoGrain, 1)
            assert await g.ping(0) == 0
            h0 = cluster.client.hot_hits
            for i in range(20):
                await g.ping(i)
            engaged = cluster.client.hot_hits - h0 == 20
            assert engaged is expect_hot, (rate, engaged)


# ----------------------------------------------------------------------
# Adaptive tail threshold (trace_tail_auto) — ISSUE 6 satellite
# ----------------------------------------------------------------------
def test_latency_policy_auto_threshold_adapts_down_and_retains_outlier():
    """Auto mode converges slow_threshold onto the root-duration
    percentile cut: a badly hand-set threshold (10s) self-tunes down to
    the workload's actual latency band, after which a real outlier
    retains while the uniform baseline keeps dropping."""
    pol = LatencyErrorPolicy(slow_threshold=10.0, auto=True)
    c = SpanCollector("s", tail=True, tail_window=0.0, policy=pol)

    def one(dur):
        root = c.open("op", "client", trace_id=c.new_trace_id(),
                      parent_id=None)
        c.close(root, duration=dur)
        c.flush_tail(force=True)

    for _ in range(64):
        one(0.01)                       # uniform fast workload
    assert c.retention_stats()["kept"] == 0   # strictly-above: all drop
    assert pol.slow_threshold < 0.1           # converged down from 10.0
    one(0.2)                                  # 20x outlier
    assert c.retention_stats()["kept"] == 1
    root = [s for s in c.snapshot() if s["parent_id"] is None][0]
    assert root["attrs"]["retained"] == "slow_auto"


def test_latency_policy_auto_uses_static_threshold_until_warm():
    """Below _MIN_HISTORY roots the configured static threshold applies
    unchanged (no percentile to tune against yet)."""
    pol = LatencyErrorPolicy(slow_threshold=0.05, auto=True)
    c = SpanCollector("s", tail=True, tail_window=0.0, policy=pol)
    root = c.open("op", "client", trace_id=c.new_trace_id(),
                  parent_id=None)
    c.close(root, duration=0.2)   # > static threshold, history cold
    c.flush_tail(force=True)
    assert c.retention_stats()["kept"] == 1
    assert pol.slow_threshold == 0.05  # untouched before warm-up


async def test_tail_auto_knob_wires_through_silo_config():
    from orleans_tpu.runtime import SiloBuilder

    silo = (SiloBuilder().with_name("auto-tail")
            .with_config(trace_enabled=True, trace_tail_enabled=True,
                         trace_tail_auto=True).build())
    assert silo.tracer.policy.auto is True


# ----------------------------------------------------------------------
# Local-trace pull skip ("went remote" hint) — ISSUE 6 satellite
# ----------------------------------------------------------------------
async def test_retention_pull_skipped_for_local_trace_and_runs_for_remote():
    fetched = []

    async def fetcher(tid):
        fetched.append(tid)
        return []

    pol = LatencyErrorPolicy(slow_threshold=1e-9)  # keep everything
    c = SpanCollector("s", tail=True, tail_window=0.0, policy=pol)
    c.remote_fetcher = fetcher

    # trace 1: never marked remote -> retained WITHOUT fanning the pull
    t1 = c.new_trace_id()
    c.close(c.open("local", "client", t1, None), duration=0.01)
    c.flush_tail(force=True)
    await c.drain_tail()
    assert c.retention_stats()["kept"] == 1
    assert c.retention_stats()["pull_skipped"] == 1
    assert fetched == []

    # trace 2: marked remote BEFORE any span closed (hint path) -> pulled
    t2 = c.new_trace_id()
    c.mark_remote(t2)
    c.close(c.open("remote", "client", t2, None), duration=0.01)
    c.flush_tail(force=True)
    await c.drain_tail()
    assert fetched == [t2]
    assert c.retention_stats()["kept"] == 2
    assert c.retention_stats()["pull_skipped"] == 1

    # trace 3: marked remote AFTER a leg closed (live pending entry)
    t3 = c.new_trace_id()
    c.close(c.open("child", "server", t3, 7), duration=0.001)
    c.mark_remote(t3)
    c.close(c.open("root", "client", t3, None), duration=0.01)
    c.flush_tail(force=True)
    await c.drain_tail()
    assert fetched == [t2, t3]


async def test_silo_local_trace_skips_control_path_fanout():
    """A silo-rooted trace whose call never leaves the silo retains
    without the ctl_trace_spans fan-out (pull_skipped counts it); the
    spans are all local so the export is already whole."""
    cluster = (TestClusterBuilder(1).add_grains(ProxyGrain, SlowEchoGrain)
               .with_tracing(tail=True, tail_window=0.1,
                             slow_threshold=0.05, client=False)
               .build())
    async with cluster:
        silo = cluster.silos[0]
        pulls = []
        real_fetcher = silo.tracer.remote_fetcher
        assert real_fetcher is not None

        async def spying_fetcher(tid):
            pulls.append(tid)
            return await real_fetcher(tid)

        silo.tracer.remote_fetcher = spying_fetcher
        # ProxyGrain.relay roots the trace silo-side; SlowEchoGrain lives
        # on the same (only) silo, so no leg ever crosses the fabric
        assert await cluster.grain(ProxyGrain, 1).relay(1, 5) == 5
        await cluster.drain_traces()
        stats = silo.tracer.retention_stats()
        assert stats["kept"] >= 1
        assert stats["pull_skipped"] >= 1
        assert pulls == []  # the fan-out never ran
        # the retained trace is complete: root + callee server turn
        spans = silo.tracer.snapshot()
        tids = {s["trace_id"] for s in spans if s["parent_id"] is None}
        assert any(s["kind"] == "server" and s["trace_id"] in tids
                   for s in spans)
