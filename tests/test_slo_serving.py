"""The interpretation layer on top of obs: SLO engine burn-rate
grading, the continuous profiler, OTLP span export (round-trip
fidelity is an acceptance criterion), the open-loop serving harness,
the ingress `slo` frame / --dump-slo CLI, the ledger → histogram
bridge, plus two satellites pinned here: the Prometheus histogram
exposition golden format and FlightRecorder.dump() racing concurrent
record() writers across a ring wrap.
"""
import json
import threading
import time

import pytest

from fluidframework_tpu.obs import metrics as obs_metrics
from fluidframework_tpu.obs.flight_recorder import FlightRecorder
from fluidframework_tpu.obs.metrics import MetricsRegistry
from fluidframework_tpu.obs.profiler import (
    ContinuousProfiler,
    component_of,
    device_trace,
    device_trace_enabled,
    set_device_trace,
)
from fluidframework_tpu.obs.slo import (
    DEFAULT_FAST_WINDOW_S,
    DEFAULT_SLOW_WINDOW_S,
    Objective,
    SloEngine,
)
from fluidframework_tpu.obs.spans import (
    FileSpanExporter,
    format_spans,
    op_to_otlp,
    otlp_to_hops,
    trace_id_for,
)
from fluidframework_tpu.obs.trace import stamp


class ManualClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t


def _latency_rig(target=0.9, threshold=10.0,
                 buckets=(1.0, 10.0, 100.0)):
    """Fresh registry + histogram + engine on a manual clock. Windows
    are 10s fast / 120s slow (the production 1:12 ratio, scaled)."""
    reg = MetricsRegistry()
    hist = reg.histogram("rig_lat_ms", "h", buckets=buckets)
    clock = ManualClock()
    engine = SloEngine(
        [Objective("lat", metric="rig_lat_ms",
                   threshold_ms=threshold, target=target)],
        fast_window_s=10.0, slow_window_s=120.0,
        clock=clock, registry=reg,
    )
    return reg, hist._solo(), clock, engine


# ======================================================================
# Objective validation (the runtime half of slo-unbound-objective)


def test_objective_validates_kind_target_and_required_fields():
    with pytest.raises(ValueError, match="unknown objective kind"):
        Objective("x", metric="m",  # fluidlint: disable=slo-unbound-objective -- negative fixture
                  kind="vibes")
    with pytest.raises(ValueError, match="target"):
        Objective("x", metric="m",  # fluidlint: disable=slo-unbound-objective -- negative fixture
                  target=1.0)
    with pytest.raises(ValueError, match="needs metric"):
        Objective("x")
    with pytest.raises(ValueError, match="good_metric"):
        Objective("x", kind="goodput")


def test_engine_rejects_unbound_or_wrong_kind_metric():
    reg = MetricsRegistry()
    reg.counter("a_total", "c")
    # (these are the lint rule's OWN negative fixtures, hence the
    # inline disables: the static half must keep flagging exactly
    # these shapes, the runtime half is what's under test here)
    with pytest.raises(ValueError, match="slo-unbound-objective"):
        SloEngine([Objective("x", metric="nope_ms")],  # fluidlint: disable=slo-unbound-objective -- negative fixture
                  registry=reg)
    # registered, but a counter where a histogram is required
    with pytest.raises(ValueError, match="not a registered histogram"):
        SloEngine([Objective("x", metric="a_total")],  # fluidlint: disable=slo-unbound-objective -- negative fixture
                  registry=reg)
    with pytest.raises(ValueError, match="not a .*registered counter"):
        SloEngine([Objective("x", kind="goodput",  # fluidlint: disable=slo-unbound-objective -- negative fixture
                             good_metric="a_total",
                             total_metric="nope_total")], registry=reg)


def test_engine_rejects_duplicates_and_bad_windows():
    reg = MetricsRegistry()
    reg.histogram("d_ms", "h")
    obj = Objective("x", metric="d_ms", threshold_ms=5.0)
    engine = SloEngine([obj], registry=reg)
    with pytest.raises(ValueError, match="duplicate"):
        engine.add_objective(obj)
    with pytest.raises(ValueError, match="windows"):
        SloEngine(fast_window_s=100.0, slow_window_s=10.0)


def test_latency_threshold_snaps_up_to_a_bucket_bound():
    reg = MetricsRegistry()
    reg.histogram("s_ms", "h", buckets=(1.0, 10.0, 100.0))
    engine = SloEngine(
        [Objective("snap", metric="s_ms", threshold_ms=42.0)],
        registry=reg,
    )
    engine.tick()
    (rec,) = engine.evaluate()["objectives"]
    assert rec["threshold_ms"] == 42.0
    assert rec["effective_threshold_ms"] == 100.0
    # a threshold above every bucket cannot be graded at all
    with pytest.raises(ValueError, match="above every bucket"):
        SloEngine(
            [Objective("over", metric="s_ms", threshold_ms=1e9)],
            registry=reg,
        )


# ======================================================================
# burn-rate math and verdict transitions


def test_burn_rate_is_bad_fraction_over_error_budget():
    _reg, hist, clock, engine = _latency_rig(target=0.9)
    engine.tick()
    for _ in range(90):
        hist.observe(5.0)    # good (<= 10ms)
    for _ in range(10):
        hist.observe(50.0)   # bad
    clock.t = 5.0
    engine.tick()
    (rec,) = engine.evaluate()["objectives"]
    # bad fraction 0.1 against an error budget of 0.1 -> burn 1.0:
    # consuming exactly the budget is NOT a breach (> , not >=)
    assert rec["fast"]["bad"] == 10.0
    assert rec["fast"]["total"] == 100.0
    assert rec["fast"]["burn"] == pytest.approx(1.0)
    assert rec["verdict"] == "ok"


def test_verdict_ladder_ok_warn_breach_and_breach_counter():
    reg, hist, clock, engine = _latency_rig(target=0.9)
    breach_metric = reg  # silence linters; counter read via engine reg
    del breach_metric
    engine.tick()
    # healthy traffic for the whole slow window
    for t in range(12):
        clock.t = 10.0 * (t + 1)
        for _ in range(10):
            hist.observe(1.0)
        engine.tick()
    (rec,) = engine.evaluate()["objectives"]
    assert rec["verdict"] == "ok"

    # acute breakage: the FAST window burns, the slow one (diluted by
    # 120s of healthy history) does not -> warn
    t0 = clock.t
    clock.t = t0 + 5.0
    for _ in range(10):
        hist.observe(500.0)
    engine.tick()
    (rec,) = engine.evaluate()["objectives"]
    assert rec["fast"]["burn"] > 1.0
    assert rec["slow"]["burn"] <= 1.0
    assert rec["verdict"] == "warn"

    # sustained breakage: both windows burn -> breach
    for t in range(12):
        clock.t += 10.0
        for _ in range(10):
            hist.observe(500.0)
        engine.tick()
    (rec,) = engine.evaluate()["objectives"]
    assert rec["verdict"] == "breach"


def test_breach_total_increments_once_per_transition():
    _reg, hist, clock, engine = _latency_rig(target=0.5)
    breach = obs_metrics.REGISTRY.get("slo_breach_total")
    child = breach.labels(objective="lat")
    before = child.value
    engine.tick()
    # everything bad in both windows -> breach
    clock.t = 1.0
    for _ in range(10):
        hist.observe(500.0)
    engine.tick()
    assert engine.evaluate()["objectives"][0]["verdict"] == "breach"
    assert child.value == before + 1
    # still breached: no double-count
    clock.t = 2.0
    for _ in range(10):
        hist.observe(500.0)
    engine.tick()
    assert engine.evaluate()["objectives"][0]["verdict"] == "breach"
    assert child.value == before + 1
    # recovery (windows age the bad events out), then re-breach
    clock.t = 300.0
    engine.tick()
    assert engine.evaluate()["objectives"][0]["verdict"] == "ok"
    clock.t = 301.0
    for _ in range(10):
        hist.observe(500.0)
    engine.tick()
    assert engine.evaluate()["objectives"][0]["verdict"] == "breach"
    assert child.value == before + 2


def test_breach_latch_holds_through_warn_no_dump_storm():
    """An objective oscillating breach <-> warn at the threshold must
    not re-count the breach or re-dump the recorders on every swing:
    the latch clears on OK only."""
    _reg, hist, clock, engine = _latency_rig(target=0.9)
    breach = obs_metrics.REGISTRY.get("slo_breach_total")
    child = breach.labels(objective="lat")
    before = child.value
    dumps = []

    class Target:
        def dump_to(self, reason=""):
            dumps.append(reason)

    engine.add_dump_target(Target())
    engine.tick()
    for _ in range(10):
        hist.observe(500.0)
    clock.t = 1.0
    engine.tick()
    assert engine.evaluate()["objectives"][0]["verdict"] == "breach"
    assert child.value == before + 1 and len(dumps) == 1
    # heavy GOOD traffic dilutes the slow window below burn 1 while
    # fresh bad events keep the fast window burning -> warn
    for _ in range(185):
        hist.observe(1.0)
    clock.t = 50.0
    engine.tick()
    clock.t = 95.0
    engine.tick()
    for _ in range(5):
        hist.observe(500.0)
    clock.t = 100.0
    engine.tick()
    (rec,) = engine.evaluate()["objectives"]
    assert rec["verdict"] == "warn", rec
    # the slow window re-burns -> breach again; the latch held
    # through the warn, so NO second count and NO second dump
    for _ in range(30):
        hist.observe(500.0)
    clock.t = 101.0
    engine.tick()
    assert engine.evaluate()["objectives"][0]["verdict"] == "breach"
    assert child.value == before + 1
    assert len(dumps) == 1


def test_cumulative_clamps_nonatomic_histogram_reads():
    """count and count_le are read non-atomically against concurrent
    observers; a momentary good > total must clamp to bad=0, never
    store a negative bad count in the sample ring."""
    reg = MetricsRegistry()
    h = reg.histogram("cl_ms", "h", buckets=(1.0, 10.0))
    engine = SloEngine(
        [Objective("lat", metric="cl_ms", threshold_ms=10.0)],
        fast_window_s=10.0, slow_window_s=120.0, registry=reg,
    )
    child = h._solo()
    for _ in range(5):
        child.observe(0.5)
    # simulate the torn read: total observed before a racing good
    # observation that count_le already sees
    child.count = 4
    bad, total = engine._bound["lat"].cumulative()
    assert bad == 0.0 and total == 4.0


def test_goodput_objective_and_empty_window_reads_zero_burn():
    reg = MetricsRegistry()
    good = reg.counter("g_total", "c")._solo()
    total = reg.counter("t_total", "c")._solo()
    clock = ManualClock()
    engine = SloEngine(
        [Objective("gp", kind="goodput", good_metric="g_total",
                   total_metric="t_total", target=0.9)],
        fast_window_s=10.0, slow_window_s=120.0,
        clock=clock, registry=reg,
    )
    # nothing served: burn 0, verdict ok (a stalled service surfaces
    # through its OFFERED counter staying flat, not a div-by-zero)
    engine.tick()
    (rec,) = engine.evaluate()["objectives"]
    assert rec["fast"]["burn"] == 0.0 and rec["verdict"] == "ok"

    engine.tick()
    total.inc(100)
    good.inc(60)  # 40% shed >> 10% budget
    clock.t = 5.0
    engine.tick()
    (rec,) = engine.evaluate()["objectives"]
    assert rec["fast"]["bad"] == 40.0
    assert rec["fast"]["burn"] == pytest.approx(4.0)


def test_breach_dumps_flight_recorders_and_context_rides_report():
    _reg, hist, clock, engine = _latency_rig(target=0.5)
    flight = FlightRecorder(capacity=8, name="t")
    flight.record("round", n=1)
    dumped = []
    flight_dump_to = flight.dump_to

    class Target:
        def dump_to(self, reason=""):
            dumped.append(reason)
            flight_dump_to(reason=reason)

    engine.add_dump_target(Target())
    engine.add_context("tier", lambda: "severe")
    engine.add_context("broken", lambda: 1 / 0)
    engine.tick()
    clock.t = 1.0
    for _ in range(4):
        hist.observe(500.0)
    engine.tick()
    report = engine.evaluate()
    assert report["context"]["tier"] == "severe"
    # a context source raising must not kill the report
    assert report["context"]["broken"] == "<error: ZeroDivisionError>"
    assert dumped == ["slo breach: lat"]
    # still breached on the next evaluation: no dump storm
    clock.t = 2.0
    engine.tick()
    engine.evaluate()
    assert dumped == ["slo breach: lat"]


def test_maybe_tick_rate_limits_and_report_is_tick_plus_evaluate():
    _reg, hist, clock, engine = _latency_rig()
    engine.maybe_tick()
    engine.maybe_tick()  # same instant: coalesced
    assert len(engine._samples["lat"]) == 1
    clock.t = 2.0
    engine.maybe_tick()
    assert len(engine._samples["lat"]) == 2
    hist.observe(1.0)
    clock.t = 3.0
    report = engine.report()
    assert len(engine._samples["lat"]) == 3
    assert report["objectives"][0]["verdict"] == "ok"
    assert report["fast_window_s"] == 10.0


def test_default_windows_keep_the_5m_1h_shape():
    assert DEFAULT_FAST_WINDOW_S == 300.0
    assert DEFAULT_SLOW_WINDOW_S == 3600.0


# ======================================================================
# continuous profiler


def test_component_of_maps_thread_name_prefixes():
    assert component_of("socket-recv-7") == "driver-recv"
    assert component_of("socket-dispatch-x") == "driver-dispatch"
    assert component_of("ingress-loop") == "ingress"
    assert component_of("serve-bench-main") == "harness"
    assert component_of("MainThread") == "main"
    assert component_of("weird-thread") == "other"


def test_profiler_attributes_samples_by_thread_name():
    stop = threading.Event()

    def spin():
        while not stop.wait(0.0005):
            pass

    worker = threading.Thread(target=spin, daemon=True,
                              name="socket-recv-prof-test")
    worker.start()
    prof = ContinuousProfiler(interval_s=0.002, name="t")
    try:
        with prof:
            time.sleep(0.25)
    finally:
        stop.set()
        worker.join(timeout=5)
    assert prof.samples > 10
    by_comp = prof.by_component()
    # the spinning worker must be attributed to its component
    assert by_comp.get("driver-recv", 0) > 0
    top = prof.top(5, component="driver-recv")
    assert top and all(r["component"] == "driver-recv" for r in top)
    summary = prof.summary()
    assert summary["samples"] == prof.samples
    assert summary["overhead_pct"] < 50.0  # own cost, sane bound
    text = prof.dump(reason="unit")
    assert "profiler[t] dump (unit)" in text
    assert "driver-recv" in text


def test_profiler_flushes_batched_counts_to_registry_on_stop():
    fam = obs_metrics.REGISTRY.get("profiler_samples_total")
    before = sum(
        c.value for c in fam._children.values()
    ) if fam._children else 0.0
    prof = ContinuousProfiler(interval_s=0.002, name="t2")
    prof.start()
    time.sleep(0.1)
    prof.stop()
    after = sum(c.value for c in fam._children.values())
    # one flush covering every sample taken — NOT one inc per sample
    # on the hot sampling loop (that contention was a measured 7%
    # serving overhead; batching is load-bearing)
    assert after - before >= prof.samples > 0
    # idempotent stop, restartable
    prof.stop()
    assert not prof.running


def test_profiler_validates_interval_and_dump_to_writes_stream():
    import io

    with pytest.raises(ValueError):
        ContinuousProfiler(interval_s=0.0)
    prof = ContinuousProfiler(interval_s=0.002)
    buf = io.StringIO()
    text = prof.dump_to(reason="empty", stream=buf)
    assert "0 sample(s)" in text
    assert buf.getvalue().strip() == text.strip()


@pytest.mark.parametrize("switch", ["env", "flag"])
def test_device_trace_is_a_noop_unless_enabled(monkeypatch, switch):
    monkeypatch.delenv("FFTPU_DEVICE_TRACE", raising=False)
    set_device_trace(False)
    assert not device_trace_enabled()
    with device_trace("round"):
        x = 1
    assert x == 1
    # enabled either way: still must not raise (jax present in this env)
    if switch == "env":
        monkeypatch.setenv("FFTPU_DEVICE_TRACE", "1")
    else:
        set_device_trace(True)
    try:
        assert device_trace_enabled()
        with device_trace("round"):
            x = 2
        assert x == 2
    finally:
        set_device_trace(False)
    monkeypatch.delenv("FFTPU_DEVICE_TRACE", raising=False)
    assert not device_trace_enabled()


# ======================================================================
# span export (round-trip fidelity = acceptance criterion)


def _sample_traces():
    # full-precision wall-clock floats plus an awkward irrational
    # fraction: exactly what integer-nano conversion would corrupt
    t0 = 1722700000.123456789
    traces = stamp([], "client", "submit", timestamp=t0)
    stamp(traces, "ingress", "receive", timestamp=t0 + 0.002)
    stamp(traces, "sequencer", "ticket", timestamp=t0 + 0.0301)
    stamp(traces, "client", "ack", timestamp=t0 + 1 / 3)
    return traces


def test_otlp_round_trip_is_bit_exact():
    traces = _sample_traces()
    doc = op_to_otlp(traces, document_id="doc", client_id="c1", csn=7)
    # through the serialized form, like a real file export
    doc2 = json.loads(json.dumps(doc))
    back = otlp_to_hops(doc2)
    assert [(t.service, t.action, t.timestamp) for t in back] == \
        [(t.service, t.action, t.timestamp) for t in traces]
    # timestamps are FLOAT-identical, not just close
    assert all(a.timestamp == b.timestamp
               for a, b in zip(back, traces))


def test_otlp_shape_root_plus_child_spans_with_deterministic_ids():
    traces = _sample_traces()
    doc = op_to_otlp(traces, document_id="doc", client_id="c1", csn=7)
    (rs,) = doc["resourceSpans"]
    assert rs["resource"]["attributes"][0]["value"]["stringValue"] \
        == "fluidframework-tpu"
    (ss,) = rs["scopeSpans"]
    spans = ss["spans"]
    assert len(spans) == 1 + len(traces)
    root, children = spans[0], spans[1:]
    assert "parentSpanId" not in root
    tid = trace_id_for("doc", "c1", 7)
    assert root["traceId"] == tid and len(tid) == 32
    assert all(c["parentSpanId"] == root["spanId"] for c in children)
    assert children[0]["name"] == "client:submit"
    # nano timestamps are decimal strings (protobuf-JSON fixed64)
    assert root["startTimeUnixNano"].isdigit()
    # child k's window starts at hop k-1's stamp
    assert children[1]["startTimeUnixNano"] == \
        children[0]["endTimeUnixNano"]
    # byte-deterministic: same op -> same document
    assert json.dumps(doc, sort_keys=True) == json.dumps(
        op_to_otlp(traces, document_id="doc", client_id="c1", csn=7),
        sort_keys=True,
    )
    # a different op gets a different trace id
    assert op_to_otlp(traces, document_id="doc", client_id="c1",
                      csn=8)["resourceSpans"][0]["scopeSpans"][0][
        "spans"][0]["traceId"] != tid


def test_file_span_exporter_round_trips_through_disk(tmp_path):
    path = tmp_path / "spans.jsonl"
    exporter = FileSpanExporter(str(path))
    traces = _sample_traces()
    exporter.export(traces, document_id="d", client_id="c", csn=1)
    exporter.export(traces[:2], document_id="d", client_id="c", csn=2)
    assert exporter.exported == 2
    docs = exporter.read_back()
    assert len(docs) == 2
    back = otlp_to_hops(docs[0])
    assert [(t.service, t.action, t.timestamp) for t in back] == \
        [(t.service, t.action, t.timestamp) for t in traces]
    assert len(otlp_to_hops(docs[1])) == 2


def test_span_export_empty_and_format_spans():
    assert op_to_otlp([], document_id="d", client_id="c", csn=0)[
        "resourceSpans"][0]["scopeSpans"][0]["spans"] == []
    assert otlp_to_hops({"resourceSpans": []}) == []
    assert format_spans([]) == "(no spans)"
    text = format_spans(_sample_traces())
    assert "client:submit" in text and "sequencer:ticket" in text


# ======================================================================
# satellite: Prometheus histogram exposition golden format


def test_prometheus_exposition_golden_format():
    reg = MetricsRegistry()
    c = reg.counter("req_total", "requests served",
                    labelnames=("route",))
    c.labels(route="host").inc(3)
    g = reg.gauge("depth", "queue depth")
    g.set(7.5)
    h = reg.histogram("lat_ms", "latency", labelnames=("route",),
                      buckets=(1.0, 2.5))
    child = h.labels(route="host")
    child.observe(0.5)
    child.observe(2.0)
    child.observe(99.0)
    # the exposition contract (Prometheus text format 0.0.4):
    # cumulative le-labelled _bucket lines ending in +Inf, plus
    # _sum/_count, HELP/TYPE per family — pinned as a GOLDEN string
    # so any renderer drift is a loud diff
    assert reg.render_prometheus() == (
        "# HELP depth queue depth\n"
        "# TYPE depth gauge\n"
        "depth 7.5\n"
        "# HELP lat_ms latency\n"
        "# TYPE lat_ms histogram\n"
        'lat_ms_bucket{route="host",le="1.0"} 1\n'
        'lat_ms_bucket{route="host",le="2.5"} 2\n'
        'lat_ms_bucket{route="host",le="+Inf"} 3\n'
        'lat_ms_sum{route="host"} 101.5\n'
        'lat_ms_count{route="host"} 3\n'
        "# HELP req_total requests served\n"
        "# TYPE req_total counter\n"
        'req_total{route="host"} 3.0\n'
    )


def test_prometheus_exposition_escapes_label_values_and_help():
    reg = MetricsRegistry()
    c = reg.counter("esc_total", 'tricky "help" with \\ and\nnewline',
                    labelnames=("k",))
    c.labels(k='a"b\\c\nd').inc()
    text = reg.render_prometheus()
    assert ("# HELP esc_total tricky \"help\" with \\\\ and\\n"
            "newline\n") in text
    assert 'esc_total{k="a\\"b\\\\c\\nd"} 1.0\n' in text


def test_histogram_count_le_is_exact_on_bucket_bounds():
    reg = MetricsRegistry()
    h = reg.histogram("cle_ms", "h", buckets=(1.0, 10.0))._solo()
    for v in (0.5, 5.0, 50.0):
        h.observe(v)
    assert h.count_le(1.0) == 1
    assert h.count_le(10.0) == 2
    # between bounds: conservative (largest bound <= ask)
    assert h.count_le(9.0) == 1
    assert h.count_le(0.1) == 0


# ======================================================================
# satellite: FlightRecorder.dump() racing record() across ring wrap


def test_flight_recorder_dump_races_concurrent_writers():
    """The lock-free claim, asserted: dump()/events() racing N
    writers that wrap the ring thousands of times never raises,
    never yields a torn event, and keeps indices strictly
    increasing. (A reader may see a torn WINDOW — old + new events
    mixed — but each EVENT is a single tuple store.)"""
    flight = FlightRecorder(capacity=64, name="race")
    n_writers, per_writer = 4, 3000
    start = threading.Barrier(n_writers + 1)
    errors = []

    def writer(wid):
        try:
            start.wait(timeout=10)
            for n in range(per_writer):
                flight.record(f"w{wid}", wid=wid, n=n)
        except Exception as e:  # pragma: no cover - the assertion
            errors.append(e)

    threads = [
        threading.Thread(target=writer, args=(w,), daemon=True)
        for w in range(n_writers)
    ]
    for t in threads:
        t.start()
    start.wait(timeout=10)
    dumps = 0
    while any(t.is_alive() for t in threads):
        text = flight.dump(reason="mid-race")
        assert "flight-recorder[race]" in text
        events = flight.events()
        # indices strictly increasing = no duplicate/zombie slots
        indices = [e[0] for e in events]
        assert indices == sorted(set(indices))
        for _i, _ts, kind, fields in events:
            # torn-event check: kind and fields written together
            assert kind == f"w{fields['wid']}", (kind, fields)
            assert 0 <= fields["n"] < per_writer
        dumps += 1
    for t in threads:
        t.join(timeout=10)
    assert not errors
    assert dumps > 0
    assert flight.recorded == n_writers * per_writer
    # post-race: ring holds exactly capacity, the newest tail
    final = flight.events()
    assert len(final) == 64
    assert final[-1][0] == n_writers * per_writer - 1
    assert "older overwritten" in flight.dump()


# ======================================================================
# ledger → histogram bridge (runtime/op_lifecycle.py)


def test_op_ledger_bridges_hops_into_labelled_histograms():
    from fluidframework_tpu.runtime.op_lifecycle import OpLatencyLedger

    hop_fam = obs_metrics.REGISTRY.get("op_hop_ms")
    e2e = obs_metrics.REGISTRY.get("op_submit_ack_ms")._solo()
    before_e2e = e2e.count
    ledger = OpLatencyLedger(capacity=4)
    traces = stamp([], "client", "submit", timestamp=100.0)
    stamp(traces, "sequencer", "ticket", timestamp=100.010)
    stamp(traces, "client", "ack", timestamp=100.025)
    entry = ledger.record(1, 501, traces)
    assert entry["total_ms"] == pytest.approx(25.0)
    ticket = hop_fam.labels(hop="sequencer:ticket")
    assert ticket.count >= 1
    assert e2e.count == before_e2e + 1
    # an SLO objective can bind to one hop's budget (the per-hop
    # latency-budget framing the ISSUE cites)
    engine = SloEngine([Objective(
        "ticket-hop", metric="op_hop_ms",
        labels={"hop": "sequencer:ticket"}, threshold_ms=50.0,
    )])
    engine.tick()
    (rec,) = engine.evaluate()["objectives"]
    assert rec["verdict"] == "ok"


# ======================================================================
# pressure context (qos/pressure.py → SLO report)


def test_pressure_monitor_records_tier_transitions_for_context():
    from fluidframework_tpu.qos.pressure import PressureMonitor

    clock = ManualClock()
    depth = {"v": 0.0}
    mon = PressureMonitor(clock=clock, min_interval_s=0.0)
    mon.add_source("q", lambda: depth["v"], capacity=100.0)
    assert mon.context()["tier_name"] == "nominal"
    depth["v"] = 75.0
    clock.t = 1.0
    mon.sample()
    depth["v"] = 99.0
    clock.t = 2.0
    mon.sample()
    depth["v"] = 10.0
    clock.t = 3.0
    ctx = mon.context()
    assert ctx["tier_name"] == "nominal"
    trail = ctx["recent_transitions"]
    assert [t["to"] for t in trail][-1] == "nominal"
    assert len(trail) >= 3  # up through the tiers and back down
    assert ctx["transition_counts"]["nominal"] >= 1
    assert ctx["by_source"]["q"] == pytest.approx(0.1)


# ======================================================================
# open-loop serving harness (tools/serve_bench.py)


def _tiny(**kw):
    from fluidframework_tpu.tools.serve_bench import ServeBenchConfig

    base = dict(n_docs=8, readers_per_doc=2, duration_s=1.5,
                tick_s=0.05, capacity_ops_per_s=100.0,
                offered_multiple=0.8, seed=7, sidecar_docs=0)
    base.update(kw)
    return ServeBenchConfig(**base)


def test_serve_bench_steady_state_holds_objectives_and_is_deterministic():
    from fluidframework_tpu.tools.serve_bench import run_serve_bench

    r1 = run_serve_bench(_tiny())
    r2 = run_serve_bench(_tiny())
    assert r1.deterministic_fields() == r2.deterministic_fields()
    assert r1.offered_ops > 50
    assert r1.acked_ops == r1.offered_ops - r1.shed_ops - \
        r1.backlog_final
    assert r1.sessions == 8 * 3  # writer + 2 readers per doc
    verdicts = {o["name"]: o["verdict"]
                for o in r1.slo_report["objectives"]}
    assert verdicts == {"submit-ack-p99": "ok", "goodput-floor": "ok"}
    assert r1.slo_breached_objectives == []
    assert r1.latency_p99_ms is not None
    assert r1.latency_p99_ms < 100.0  # under the default budget
    # the report cites the qos pressure context
    assert r1.slo_report["context"]["pressure"]["tier_name"] == \
        "nominal"


def test_serve_bench_overload_breaches_latency_and_goodput():
    from fluidframework_tpu.tools.serve_bench import run_serve_bench

    r = run_serve_bench(_tiny(offered_multiple=4.0, duration_s=3.0))
    assert r.offered_ops > r.acked_ops
    assert r.backlog_peak > 50  # the open loop actually queued
    assert r.latency_p99_ms > 100.0
    assert "submit-ack-p99" in r.slo_breached_objectives
    assert r.slo_breach_evaluations > 0
    # the final report's fast window is saturated with bad events
    (lat,) = [o for o in r.slo_report["objectives"]
              if o["name"] == "submit-ack-p99"]
    assert lat["verdict"] == "breach"
    assert lat["fast"]["burn"] > 1.0
    # overload context names the backlog pressure the breach rode on
    ctx = r.slo_report["context"]
    assert ctx["backlog"] > 0
    assert ctx["pressure"]["by_source"]["serve_backlog"] > 0.0


def test_serve_bench_sidecar_route_split_grades_settle_budget():
    from fluidframework_tpu.tools.serve_bench import run_serve_bench

    r = run_serve_bench(_tiny(sidecar_docs=2, duration_s=1.0,
                              sidecar_steps=10))
    assert r.sidecar_rounds > 0
    assert r.sidecar_ops > 0
    assert 0.0 < r.route_split_sidecar < 1.0
    names = {o["name"] for o in r.slo_report["objectives"]}
    assert "sidecar-settle-p99" in names


def test_serve_bench_profiler_rides_without_changing_the_sim():
    from fluidframework_tpu.tools.serve_bench import run_serve_bench

    off = run_serve_bench(_tiny())
    on = run_serve_bench(_tiny(profile=True))
    assert on.deterministic_fields() == off.deterministic_fields()
    assert on.profiler is not None and off.profiler is None
    assert on.profiler["samples"] > 0
    # the driving thread is attributed to the harness component
    assert on.profiler["by_component"].get("harness", 0) > 0


# ======================================================================
# ingress slo frame + --dump-slo CLI


def test_ingress_slo_frame_and_dump_cli(alfred, capsys):
    import socket as socket_mod

    from fluidframework_tpu.service.__main__ import dump_slo
    from fluidframework_tpu.service.ingress import (
        pack_frame,
        recv_frame_blocking,
    )

    # without --slo: the frame answers with a pointer, the CLI exits 1
    server = alfred()
    with socket_mod.create_connection(
            ("127.0.0.1", server.port), timeout=10) as sock:
        sock.sendall(pack_frame({"type": "slo", "rid": 3}))
        frame = recv_frame_blocking(sock)
    assert frame["type"] == "slo" and frame["rid"] == 3
    assert frame["report"] is None
    assert "--slo" in frame["message"]
    assert dump_slo(f"127.0.0.1:{server.port}") == 1


def test_ingress_slo_frame_reports_default_objectives(alfred, capsys):
    from fluidframework_tpu.service.__main__ import dump_slo
    from fluidframework_tpu.service.ingress import (
        default_slo_objectives,
    )
    from fluidframework_tpu.obs.slo import SloEngine

    # the default objectives BIND (the runtime half of the lint rule
    # holds for the service plane's own declarations)
    engine = SloEngine(default_slo_objectives())
    server = alfred(slo=engine)
    assert dump_slo(f"127.0.0.1:{server.port}") == 0
    report = json.loads(capsys.readouterr().out)
    names = {o["name"] for o in report["objectives"]}
    assert names == {"ingress-dispatch-p99", "ingress-goodput"}
    for o in report["objectives"]:
        assert o["verdict"] in ("ok", "warn", "breach")


def test_goodput_numerator_excludes_nacked_ops():
    """A decoded-but-nacked op (read-mode submit) counts as offered
    but NOT ticketed — an all-nacked fleet must read as goodput 0,
    not 100%."""
    from fluidframework_tpu.service.ingress import (
        AlfredServer,
        _ClientSession,
    )

    server = AlfredServer()
    s = _ClientSession(server, None)
    server._sessions.add(s)
    server._dispatch(s, {
        "type": "connect_document", "document_id": "gp-doc",
        "client_id": "reader", "mode": "read",
        "versions": ["1.2", "1.1", "1.0"],
    })
    offered = obs_metrics.REGISTRY.get(
        "ingress_ops_offered_total")._solo()
    ticketed = obs_metrics.REGISTRY.get(
        "ingress_ops_ticketed_total")._solo()
    o0, t0 = offered.value, ticketed.value
    server._dispatch(s, {
        "type": "submitOp", "document_id": "gp-doc",
        "op": {
            "client_sequence_number": 1,
            "reference_sequence_number": 0,
            "type": 2, "contents": {"k": "v"},
            "metadata": None, "traces": [],
        },
    })
    assert offered.value == o0 + 1
    assert ticketed.value == t0


def test_dispatch_path_ticks_the_engine_and_times_frames(alfred):
    import socket as socket_mod

    from fluidframework_tpu.service.ingress import (
        default_slo_objectives,
        pack_frame,
        recv_frame_blocking,
    )
    from fluidframework_tpu.obs.slo import SloEngine

    engine = SloEngine(default_slo_objectives())
    server = alfred(slo=engine)
    fam = obs_metrics.REGISTRY.get("ingress_dispatch_ms")
    before = fam._solo().count
    with socket_mod.create_connection(
            ("127.0.0.1", server.port), timeout=10) as sock:
        sock.sendall(pack_frame({"type": "metrics", "rid": 1}))
        recv_frame_blocking(sock)
        sock.sendall(pack_frame({"type": "slo", "rid": 2}))
        frame = recv_frame_blocking(sock)
    # every dispatched frame lands in the latency histogram the
    # default objective binds to...
    assert fam._solo().count >= before + 2
    # ...and the dispatch path's piggybacked maybe_tick populated the
    # engine's windows without any timer thread
    assert len(engine._samples["ingress-dispatch-p99"]) >= 1
    assert frame["report"]["objectives"]
