"""Observability-layer tests (ISSUE 7; docs/observability.md):
span tracing (no-op fast path, nesting, capture, Chrome trace-event
schema), cross-node clock alignment (synthetic skew + the loopback
master/worker acceptance gate), typed metrics with Prometheus
exposition (shim compatibility, label escaping, /metrics on
web_status and the serving ModelServer), MFU-gauge plumbing on a
fake device timer, and the grouped print_stats exit report.
"""

import json
import threading
import time
import urllib.request

import pytest

from veles_tpu import resilience
from veles_tpu.config import root
from veles_tpu.launcher import Launcher
from veles_tpu.observability import attribution, metrics, tracing


@pytest.fixture(autouse=True)
def _clean_observability():
    tracing.reset()
    attribution.reset()
    resilience.reset()
    root.common.observability.trace = None
    root.common.observability.peak_tflops = None
    yield
    tracing.reset()
    attribution.reset()
    resilience.reset()
    root.common.observability.trace = None
    root.common.observability.peak_tflops = None


def _get(port, path):
    with urllib.request.urlopen(
            "http://127.0.0.1:%d%s" % (port, path), timeout=10) as r:
        ctype = r.headers.get("Content-Type", "")
        body = r.read().decode()
    return body, ctype


def _post(port, path, obj):
    req = urllib.request.Request(
        "http://127.0.0.1:%d%s" % (port, path),
        data=json.dumps(obj).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=10) as r:
        return json.loads(r.read())


# -- tracing: no-op fast path ----------------------------------------------

def test_disabled_tracing_is_noop_and_shim_still_lands():
    """Tracing off (the default): span() returns the shared no-op
    singleton, zero spans are recorded — while the metrics shim
    keeps counting (metrics are passive, not gated on tracing)."""
    assert not tracing.enabled()
    s1 = tracing.span("net.send", bytes=123)
    s2 = tracing.span("worker.step")
    assert s1 is s2  # the shared singleton: no per-call allocation
    with s1:
        with tracing.span("nested"):
            # A test-unique counter name: a loopback session from an
            # earlier test unwinding on its own thread can still be
            # bumping the REAL net.* counters concurrently.
            resilience.stats.incr("net.shim_probe", 7)
    assert tracing.spans() == []
    assert tracing.begin("server.dispatch") is s1
    # The shim landed the counter in the process registry.
    assert resilience.stats.get("net.shim_probe") == 7
    assert metrics.registry.peek("net.shim_probe").value == 7


def test_span_nesting_ids_and_ring_bound():
    tracing.enable(ring=8)
    with tracing.span("outer", k=1):
        with tracing.span("inner"):
            pass
    got = {s["name"]: s for s in tracing.spans()}
    assert set(got) == {"outer", "inner"}
    assert got["inner"]["parent"] == got["outer"]["id"]
    assert got["inner"]["trace_id"] == got["outer"]["trace_id"]
    assert got["outer"]["parent"] is None
    assert got["outer"]["attrs"] == {"k": 1}
    assert got["outer"]["dur"] >= got["inner"]["dur"] >= 0
    # Ring bound: the collector never exceeds its maxlen.
    for i in range(50):
        with tracing.span("s%d" % i):
            pass
    assert len(tracing.spans()) == 8


def test_capture_isolates_thread_spans():
    """capture() diverts only THIS thread's spans — how a worker
    sharing a process with the master (loopback) ships exactly its
    own job spans."""
    tracing.enable()
    other_done = threading.Event()

    def other():
        with tracing.span("other.thread"):
            pass
        other_done.set()

    with tracing.capture() as captured:
        t = threading.Thread(target=other)
        t.start()
        assert other_done.wait(5)
        t.join()
        with tracing.span("mine"):
            pass
    assert [s["name"] for s in captured] == ["mine"]
    assert [s["name"] for s in tracing.spans()] == ["other.thread"]


def test_attach_adopts_remote_parent():
    tracing.enable()
    with tracing.attach(777, 42):
        with tracing.span("worker.step"):
            pass
    (s,) = tracing.spans()
    assert s["trace_id"] == 777 and s["parent"] == 42


# -- Chrome trace-event export ---------------------------------------------

def test_chrome_trace_schema(tmp_path):
    tracing.enable()
    with tracing.span("server.dispatch", worker="w/1"):
        with tracing.span("net.send"):
            pass
    tracing.ingest(tracing.shift(
        [{"name": "worker.step", "ts": time.time() * 1e6,
          "dur": 5.0, "id": 999, "parent": 1, "trace_id": 1,
          "tid": 4}], 0.0), proc="worker:w/1")
    path = str(tmp_path / "trace.json")
    obj = tracing.export_chrome_trace(path)
    with open(path) as fin:
        on_disk = json.load(fin)
    assert on_disk == obj
    events = obj["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    complete = [e for e in events if e["ph"] == "X"]
    # Two processes (master + the ingested worker), named.
    assert {e["args"]["name"].split(":")[0].split("/")[0]
            for e in meta} == {"master", "worker"}
    assert len(complete) == 3
    for e in complete:
        for field in ("name", "ts", "dur", "pid", "tid", "args",
                      "cat"):
            assert field in e
        assert isinstance(e["ts"], float)
        assert "span_id" in e["args"]
    by_name = {e["name"]: e for e in complete}
    # Parent/trace ids ride args; worker spans sit on their own pid.
    assert by_name["net.send"]["args"]["parent_id"] == \
        by_name["server.dispatch"]["args"]["span_id"]
    assert by_name["worker.step"]["pid"] != \
        by_name["server.dispatch"]["pid"]


# -- clock alignment -------------------------------------------------------

def test_clock_sync_aligns_synthetic_skew():
    """A worker clock 123.456 s ahead: the min-RTT midpoint estimate
    recovers the offset to within half the best RTT, and shifted
    spans land inside the master-side window."""
    skew = 123.456  # worker = master + skew
    sync = tracing.ClockSync()
    rtts = [0.080, 0.011, 0.240, 0.0030, 0.055]
    t = 1000.0  # master clock
    for rtt in rtts:
        send = t
        # Asymmetric path: the reply leg is slower — worst case for
        # the midpoint estimator, error still bounded by rtt/2.
        remote = (t + rtt * 0.3) + skew
        recv = t + rtt
        sync.sample(send, remote, recv)
        t += 1.0
    # offset = master→worker shift estimate = remote - local mid.
    assert abs(sync.offset - skew) <= 0.003 / 2 + 1e-9
    assert abs(sync.rtt - 0.0030) < 1e-9
    assert sync.samples == len(rtts)
    # Worker spans shift back onto the master timeline: a step that
    # really ran at master-time 2000.0 (worker clock 2000+skew).
    worker_span = {"name": "worker.step",
                   "ts": (2000.0 + skew) * 1e6, "dur": 1e4}
    (aligned,) = tracing.shift([worker_span], -sync.offset)
    assert abs(aligned["ts"] - 2000.0 * 1e6) <= 0.0015 * 1e6 + 1
    # A backwards exchange (clock stepped mid-sample) is discarded.
    sync.sample(10.0, 5.0, 9.0)
    assert sync.samples == len(rtts)


# -- Prometheus exposition -------------------------------------------------

def test_prometheus_exposition_format():
    reg = metrics.MetricsRegistry()
    reg.counter("net.bytes_sent").inc(4096)
    reg.gauge("device.mfu").set(0.42)
    hist = reg.histogram("serving.latency_seconds",
                         labels={"kind": 'a"b\\c\nd'},
                         buckets=(0.1, 1.0))
    hist.observe(0.05)
    hist.observe(0.5)
    hist.observe(5.0)
    text = metrics.render_prometheus([reg])
    lines = text.splitlines()
    # Every family carries its # TYPE line.
    assert "# TYPE veles_net_bytes_sent_total counter" in lines
    assert "# TYPE veles_device_mfu gauge" in lines
    assert "# TYPE veles_serving_latency_seconds histogram" in lines
    assert "veles_net_bytes_sent_total 4096" in lines
    assert "veles_device_mfu 0.42" in lines
    # Label escaping: backslash, double-quote, newline.
    esc = 'kind="a\\"b\\\\c\\nd"'
    assert 'veles_serving_latency_seconds_bucket{%s,le="0.1"} 1' \
        % esc in lines
    assert 'veles_serving_latency_seconds_bucket{%s,le="1.0"} 2' \
        % esc in lines
    assert 'veles_serving_latency_seconds_bucket{%s,le="+Inf"} 3' \
        % esc in lines
    assert "veles_serving_latency_seconds_count{%s} 3" % esc in lines
    sums = [ln for ln in lines if ln.startswith(
        "veles_serving_latency_seconds_sum")]
    assert len(sums) == 1 and abs(
        float(sums[0].rsplit(" ", 1)[1]) - 5.55) < 1e-9
    # TYPE lines precede their samples.
    assert lines.index("# TYPE veles_device_mfu gauge") < \
        lines.index("veles_device_mfu 0.42")


def test_resilience_shim_contract():
    """The PR-1 API surface, unchanged through the registry shim:
    incr/get/snapshot/reset — and snapshot stays a flat counter dict
    even when gauges/histograms share the registry."""
    stats = resilience.ResilienceStats()
    stats.incr("server.drop")
    stats.incr("server.drop", 2)
    assert stats.get("server.drop") == 3
    assert stats.get("never.seen") == 0
    stats.registry.gauge("device.mfu").set(0.5)
    stats.registry.histogram("lat").observe(1.0)
    assert stats.snapshot() == {"server.drop": 3}
    stats.reset()
    assert stats.snapshot() == {}
    # The module-global shim feeds the PROCESS registry.
    resilience.stats.incr("chaos.net.drop")
    assert metrics.registry.peek("chaos.net.drop").value == 1


# -- MFU gauge plumbing (fake device timer) --------------------------------

def test_mfu_gauge_on_fake_device_timer():
    root.common.observability.peak_tflops = 100.0  # 1e14 FLOP/s
    # One "device step": 50 ms at 25% utilization of the fake peak.
    snap = attribution.record_step(
        0.050, flops=0.25 * 100e12 * 0.050, ticks=8)
    assert snap["dispatches"] == 1 and snap["ticks"] == 8
    assert abs(snap["mfu"] - 0.25) < 1e-6
    assert abs(snap["device_ms"] - 50.0) < 1e-6
    assert metrics.registry.peek("device.dispatches").value == 1
    assert metrics.registry.peek("device.ticks").value == 8
    assert abs(metrics.registry.peek("device.mfu").value
               - 0.25) < 1e-4
    assert abs(metrics.registry.peek("device.step_ms").value
               - 50.0) < 1e-3
    # EWMA: a second, slower step moves the gauges part-way.
    attribution.record_step(0.150, flops=0.25 * 100e12 * 0.050)
    mfu2 = metrics.registry.peek("device.mfu").value
    assert mfu2 < 0.25
    summary = attribution.perf_summary()
    assert summary["dispatches"] == 2 and summary["ticks"] == 9
    assert summary["mfu"] == mfu2
    assert abs(summary["device_s_total"] - 0.2) < 1e-6


def test_perf_section_rides_heartbeat_and_dashboard():
    """The live MFU gauge reaches operators: launcher heartbeat
    "perf" section → web_status perf row (HTML-escaped) and the
    /metrics exposition."""
    from veles_tpu.web_status import WebStatusServer
    root.common.observability.peak_tflops = 100.0
    attribution.record_step(0.010, flops=40e12 * 0.010)

    class _Wf:
        name = "wf"

    launcher = Launcher()
    launcher.workflow = _Wf()
    payload = launcher.status_payload("m1")
    assert payload["perf"]["dispatches"] == 1
    assert abs(payload["perf"]["mfu"] - 0.4) < 1e-3
    # device.* counters ride perf, not the resilience row.
    assert "device.dispatches" not in payload.get("resilience", {})
    srv = WebStatusServer(host="127.0.0.1", port=0).start()
    try:
        srv.update({"id": "m1", "workflow": "<b>x</b>",
                    "mode": "master", "perf": payload["perf"]})
        page = srv.render_page()
        assert "perf" in page and "mfu" in page
        assert "<b>x</b>" not in page  # hostile name stays escaped
        assert "&lt;b&gt;x&lt;/b&gt;" in page
        body, ctype = _get(srv.port, "/metrics")
        assert ctype.startswith("text/plain")
        assert '# TYPE veles_perf_mfu gauge' in body
        assert 'veles_perf_mfu{master="m1"} 0.4' in body
    finally:
        srv.stop()


def test_step_compiler_publishes_device_time():
    """A real fused step (tiny MNIST MLP on CPU) lands device-time
    attribution: dispatch counter, tick counter, step_ms gauge —
    without a known peak, the MFU gauge stays silent."""
    from tests.test_dataplane import _mnist_pair
    wf = _mnist_pair(3, max_epochs=1)
    replies = []
    wf.note_slave_protocol("w", {})
    # two jobs: the dispatch that compiles the step stays out of the
    # step_ms gauge (PR 37), the next one sets it
    for _ in range(2):
        job = wf.generate_data_for_slave("w")
        wf.do_job(job, None, replies.append)
    assert len(replies) == 2
    assert metrics.registry.peek("device.dispatches").value >= 2
    assert metrics.registry.peek("device.step_ms").value > 0
    assert metrics.registry.peek("device.mfu") is None
    assert attribution.perf_summary()["dispatches"] >= 1


# -- the loopback acceptance gate ------------------------------------------

class _TracedMaster(object):
    """Minimal master workflow with real (sleep-modelled) work on
    both sides of the wire, so the dispatch window has honest
    margins around the worker's step."""

    checksum = "trace-loopback"
    job_limit = 4

    def __init__(self):
        self.generated = 0
        self.applied = 0

    def generate_initial_data_for_slave(self, slave):
        return None

    def generate_data_for_slave(self, slave=None):
        if self.generated >= self.job_limit:
            return None
        time.sleep(0.005)  # master-side share of the dispatch
        self.generated += 1
        return {"n": self.generated}

    def apply_data_from_slave(self, data, slave=None):
        time.sleep(0.005)  # the fold
        self.applied += 1

    def drop_slave(self, slave=None):
        pass

    def note_slave_protocol(self, slave, proto):
        self.proto = proto

    def should_stop_serving(self):
        return self.applied >= self.job_limit


class _TracedWorker(object):
    checksum = "trace-loopback"

    def apply_data_from_master(self, data):
        pass

    def note_net_proto(self, proto):
        self.proto = proto

    def do_job(self, data, update, callback):
        time.sleep(0.01)  # the step
        callback({"echo": data["n"]})


def test_loopback_trace_single_aligned_timeline(tmp_path):
    """THE acceptance gate: a master + 1 worker distributed run over
    real sockets with tracing on produces ONE Chrome-trace JSON whose
    master and worker spans share an aligned timeline — every
    worker.step span is strictly enclosed by its server.dispatch
    span after offset correction."""
    from veles_tpu.client import Client
    from veles_tpu.server import Server
    tracing.enable()
    master = _TracedMaster()
    server = Server(":0", master)
    worker = _TracedWorker()
    client = Client("127.0.0.1:%d" % server.port, worker)
    t = threading.Thread(target=client.run, daemon=True)
    t.start()
    server.wait(timeout=60)
    t.join(timeout=10)
    server.stop()
    assert master.applied == master.job_limit
    # The session negotiated the trace dialect and sampled the clock.
    assert master.proto.get("trace") is True
    assert client.clock.samples > 0
    path = str(tmp_path / "trace.json")
    obj = tracing.export_chrome_trace(path)
    with open(path) as fin:
        events = json.load(fin)["traceEvents"]
    assert events == obj["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    names = {e["name"] for e in complete}
    # The full pipeline is on the timeline.
    assert {"server.dispatch", "net.serialize", "net.send",
            "worker.step", "net.fold"} <= names
    dispatches = {e["args"]["trace_id"]: e for e in complete
                  if e["name"] == "server.dispatch"}
    steps = [e for e in complete if e["name"] == "worker.step"]
    assert len(steps) == master.job_limit
    assert len(dispatches) == master.job_limit
    master_pids = {e["pid"] for e in complete
                   if e["name"] == "server.dispatch"}
    for step in steps:
        dispatch = dispatches[step["args"]["trace_id"]]
        # One trace, two processes, one timeline: the worker's step
        # (offset-corrected at the worker) falls strictly inside its
        # dispatch window.
        assert step["pid"] not in master_pids
        assert step["args"]["parent_id"] == \
            dispatch["args"]["span_id"]
        assert dispatch["ts"] < step["ts"], \
            "dispatch must open before the worker step"
        assert step["ts"] + step["dur"] < \
            dispatch["ts"] + dispatch["dur"], \
            "dispatch must close after the worker step"


def test_async_pipelined_dispatch_spans_stay_siblings():
    """--async-slave holds overlapping dispatch windows on one
    handler thread: they must export as sibling roots (not chained
    parent/child), and each net.fold must parent under ITS OWN
    dispatch window."""
    from veles_tpu.client import Client
    from veles_tpu.server import Server
    tracing.enable()
    master = _TracedMaster()
    server = Server(":0", master)
    worker = _TracedWorker()
    client = Client("127.0.0.1:%d" % server.port, worker,
                    async_mode=True)
    t = threading.Thread(target=client.run, daemon=True)
    t.start()
    server.wait(timeout=60)
    t.join(timeout=10)
    server.stop()
    assert master.applied == master.job_limit
    spans = tracing.spans()
    dispatches = {s["id"]: s for s in spans
                  if s["name"] == "server.dispatch"}
    assert len(dispatches) == master.job_limit
    # Detached windows: every dispatch is a root of its own trace.
    assert all(s["parent"] is None for s in dispatches.values())
    assert len({s["trace_id"] for s in dispatches.values()}) == \
        len(dispatches)
    folds = [s for s in spans if s["name"] == "net.fold"]
    assert len(folds) == master.job_limit
    for fold in folds:
        owner = dispatches.get(fold["parent"])
        assert owner is not None, \
            "net.fold must parent under a dispatch window"
        assert fold["trace_id"] == owner["trace_id"]
    # Worker steps attach to distinct windows too.
    steps = [s for s in spans if s["name"] == "worker.step"]
    assert {s["trace_id"] for s in steps} == \
        {s["trace_id"] for s in dispatches.values()}


def test_legacy_session_sees_no_trace_fields():
    """A pickle-compat worker negotiated against a tracing master
    gets no trace/ts/spans fields (handshake-gated optional field)."""
    from veles_tpu.server import negotiate_protocol
    tracing.enable()
    proto, err = negotiate_protocol({"cmd": "handshake"})
    assert proto == {} and err is None
    # A capable worker does get the trace dialect...
    from veles_tpu.client import WORKER_CAPS
    proto, err = negotiate_protocol({"proto": dict(WORKER_CAPS)})
    assert proto.get("trace") is True
    # ...but not when the master is not tracing.
    tracing.disable()
    proto, err = negotiate_protocol({"proto": dict(WORKER_CAPS)})
    assert "trace" not in proto


# -- /metrics on the serving ModelServer -----------------------------------

def test_model_server_metrics_endpoint():
    from tests.test_serving import FakeModel
    from veles_tpu.restful import ModelServer
    server = ModelServer(FakeModel(), host="127.0.0.1", port=0,
                         max_batch=4).start()
    try:
        _post(server.port, "/api", {"input": [[1.0, 2.0, 3.0, 4.0]]})
        body, ctype = _get(server.port, "/metrics")
        assert ctype.startswith("text/plain")
        lines = body.splitlines()
        # Unified counters: the engine's request counter and latency
        # histogram, plus a # TYPE line per family.
        assert "veles_requests_classify_total 1" in lines
        assert "# TYPE veles_requests_classify_total counter" \
            in lines
        assert "# TYPE veles_serving_latency_seconds histogram" \
            in lines
        assert any(ln.startswith(
            "veles_serving_latency_seconds_bucket")
            for ln in lines)
        # The scrape-time gauges landed.
        assert any(ln.startswith("veles_serving_queue_depth ")
                   for ln in lines)
    finally:
        server.stop()


# -- grouped exit report ---------------------------------------------------

def test_print_stats_groups_by_prefix(caplog):
    import logging
    from tests.test_resilience import LedgerWorkflow
    resilience.stats.incr("net.bytes_sent", 1024)
    resilience.stats.incr("net.frames_sent", 2)
    resilience.stats.incr("server.drop")
    resilience.stats.incr("chaos.worker.kill", 0)  # zero: suppressed
    wf = LedgerWorkflow(Launcher())
    with caplog.at_level(logging.INFO):
        wf.print_stats()
    text = "\n".join(caplog.messages)
    assert "net:" in text and "bytes_sent=1024" in text
    assert "frames_sent=2" in text
    assert "server:" in text and "drop=1" in text
    assert "chaos" not in text  # zero-suppressed section
    # The flat format survives for greppers.
    caplog.clear()
    with caplog.at_level(logging.INFO):
        wf.print_stats(flat=True)
    flat = "\n".join(caplog.messages)
    assert "net.bytes_sent=1024" in flat and "server.drop=1" in flat
