"""Observability subsystem specs (docs/observability.md).

Tier-1 coverage for the obs package and its wiring: span tracer +
Chrome-trace export joined to serving requests by request id, Prometheus
text exposition (sanitization, counter/summary/histogram lines parse),
log-bucketed latency percentiles, the crash flight recorder under injected
faults, the Metrics read-path lock, SummaryWriter lifecycle, TFRecord
framing round-trip, and the profile_dir wiring."""

import json
import os
import re
import signal
import struct
import threading
import time
from urllib import request as urlreq

import numpy as np
import pytest

from bigdl_tpu.obs import flight, trace
from bigdl_tpu.obs.export import (MetricsServer, render_prometheus,
                                  sanitize_metric_name)
from bigdl_tpu.obs.flight import FlightRecorder
from bigdl_tpu.obs.hist import LogHistogram
from bigdl_tpu.optim.metrics import Metrics, SummaryWriter, global_metrics
from bigdl_tpu.resilience import faults
from bigdl_tpu.resilience.faults import FaultSpec
from bigdl_tpu.serving import (HttpFrontend, InferenceModel, ServingConfig,
                               ServingServer)


@pytest.fixture(autouse=True)
def _clean_obs():
    faults.clear()
    flight.global_recorder().clear()
    yield
    faults.clear()
    trace.disable()


def _echo(x):
    return np.asarray(x) * 2.0


# ---------------------------------------------------------------------------
# log-bucketed histogram
# ---------------------------------------------------------------------------

def test_log_histogram_percentiles_bounded_error():
    h = LogHistogram()
    rng = np.random.RandomState(0)
    samples = rng.exponential(0.05, size=5000)
    for v in samples:
        h.observe(v)
    assert h.n == 5000
    assert h.sum == pytest.approx(float(samples.sum()))
    for q in (50, 95, 99):
        exact = float(np.percentile(samples, q))
        approx = h.percentile(q)
        # log-bucketed with growth 2: at most one bucket (2x) of error
        assert exact / 2 <= approx <= exact * 2, (q, exact, approx)
    assert h.percentile(100) == pytest.approx(h.max)


def test_log_histogram_empty_percentile_is_nan():
    """No data must be distinguishable from a 0.0s latency: every
    percentile of an empty histogram is NaN, not 0 and not a bucket
    bound."""
    h = LogHistogram()
    for q in (0, 50, 99, 100):
        assert np.isnan(h.percentile(q)), q
    assert all(np.isnan(v) for v in h.quantiles().values())
    # snapshot of an empty histogram still renders (min/max report 0)
    snap = h.snapshot()
    assert snap["n"] == 0 and snap["min"] == 0.0 and snap["max"] == 0.0


def test_log_histogram_single_observation():
    """One sample: every percentile reports that sample (its bucket's
    upper bound clamps to the observed max == the sample)."""
    h = LogHistogram()
    h.observe(0.037)
    for q in (1, 50, 99, 100):
        assert h.percentile(q) == pytest.approx(0.037), q
    assert h.quantiles()["p50"] == pytest.approx(0.037)


def test_log_histogram_overflow_and_bad_samples():
    h = LogHistogram(base=1e-4, growth=2.0, n_buckets=4)
    h.observe(1e9)      # beyond the last bound: overflow bucket
    h.observe(-5.0)     # clock bug: clamped, never corrupts
    h.observe(float("nan"))
    h.observe(float("inf"))  # timeout sentinel: OVERFLOW, never underflow
    assert h.n == 4
    assert h.counts[-1] == 2
    assert h.counts[0] == 2
    assert h.sum == pytest.approx(1e9)  # inf kept out of the mean
    snap = h.snapshot()
    assert len(snap["bounds"]) == len(snap["counts"]) - 1


# ---------------------------------------------------------------------------
# Metrics registry: locking, histograms, mirroring
# ---------------------------------------------------------------------------

def test_metrics_reads_take_lock_and_never_mutate():
    m = Metrics()
    m.add("t", 1.0)
    # a read of a missing key must not insert it (the defaultdict-indexing
    # race this PR fixes) and must not raise
    assert m.mean("missing") == 0.0
    assert m.counter("missing") == 0.0
    assert "missing" not in m.sums and "missing" not in m.counts
    assert "missing" not in m.counters


def test_metrics_concurrent_read_write():
    m = Metrics()
    stop = threading.Event()
    errors = []

    def writer(i):
        while not stop.is_set():
            m.add(f"timer.{i}", 0.001)
            m.inc(f"counter.{i}")
            m.observe(f"hist.{i}", 0.01)

    def reader():
        try:
            while not stop.is_set():
                m.summary()
                m.mean("timer.0")
                m.counter("counter.1")
                m.snapshot()
        except Exception as e:  # pragma: no cover
            errors.append(e)

    threads = [threading.Thread(target=writer, args=(i,)) for i in range(3)]
    threads += [threading.Thread(target=reader) for _ in range(3)]
    [t.start() for t in threads]
    time.sleep(0.3)
    stop.set()
    [t.join(10) for t in threads]
    assert not errors, errors


def test_metrics_counters_mirror_into_global():
    m = Metrics()
    g = global_metrics()
    base = g.counter("obs_test.mirrored_total")
    m.inc("obs_test.mirrored_total", 3)
    m.observe("obs_test.mirrored_hist_s", 0.02)
    assert m.counter("obs_test.mirrored_total") == 3
    assert g.counter("obs_test.mirrored_total") == base + 3
    assert g.percentile("obs_test.mirrored_hist_s", 50) > 0


# ---------------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------------

def test_sanitize_metric_name():
    assert sanitize_metric_name("serving.shed_requests") == \
        "serving_shed_requests"
    assert sanitize_metric_name("retries_by_cause.poisoned-batch") == \
        "retries_by_cause_poisoned_batch"
    assert sanitize_metric_name("9lives") == "_9lives"
    valid = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")
    for ugly in ("a b", "a{b}", 'a"b"', "Ж.metric", ""):
        assert valid.match(sanitize_metric_name(ugly)), ugly


_LINE = re.compile(
    r"^(?:# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (?:counter|gauge|summary|histogram)"
    r"|# HELP [a-zA-Z_:][a-zA-Z0-9_:]* \S.*"
    r"|[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{le=\"[^\"]+\"\})? "
    r"(?:[0-9.eE+-]+|\+Inf|NaN))$")


def test_render_prometheus_text_format_parses():
    m = Metrics()
    m.inc("serving.shed_requests", 2)
    m.add("step_dispatch", 0.25)
    m.add("step_dispatch", 0.35)
    for v in (0.001, 0.002, 0.004, 0.4):
        m.observe("serving.latency_s", v)
    text = render_prometheus(m)
    for line in text.strip().split("\n"):
        assert _LINE.match(line), f"unparseable exposition line: {line!r}"
    assert "# TYPE serving_shed_requests counter" in text
    assert "serving_shed_requests 2.0" in text
    assert "step_dispatch_sum 0.6" in text
    assert "step_dispatch_count 2" in text
    # histogram: cumulative bucket lines, +Inf equals the sample count
    buckets = re.findall(
        r'serving_latency_s_bucket\{le="([^"]+)"\} (\d+)', text)
    assert len(buckets) > 2
    counts = [int(c) for _, c in buckets]
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert buckets[-1][0] == "+Inf" and counts[-1] == 4
    assert "serving_latency_s_count 4" in text


def test_render_prometheus_help_lines_and_type_once():
    """# HELP rides next to # TYPE (describe() strings win over the
    framework catalog), and a family header is emitted at most once per
    scrape even when two dotted names sanitize to the same family."""
    m = Metrics()
    m.inc("obs_help.requests_total", 1)
    m.describe("obs_help.requests_total", "requests seen by the test")
    for v in (0.001, 0.4):
        m.observe("serving.latency_s", v)  # catalog help, no describe()
    # two names that collide after sanitization: the family header must
    # not be re-declared for the second one
    m.gauge("collide.name", 1.0)
    m.gauge("collide_name", 2.0)
    text = render_prometheus(m)
    assert "# HELP obs_help_requests_total requests seen by the test" \
        in text
    assert "# HELP serving_latency_s " in text
    help_then_type = text.index("# HELP obs_help_requests_total")
    assert text.index("# TYPE obs_help_requests_total counter") \
        > help_then_type
    assert text.count("# TYPE collide_name gauge") == 1
    # ... and the losing name's SAMPLE is dropped too: two series with
    # identical name+labels would fail the whole scrape at a real
    # Prometheus, which is worse than losing the shadowed series
    samples = [l for l in text.splitlines()
               if l.startswith("collide_name ")]
    assert samples == ["collide_name 1.0"]
    # every line still parses
    for line in text.strip().split("\n"):
        assert _LINE.match(line), f"unparseable exposition line: {line!r}"


def test_render_prometheus_new_perf_gauge_lines_parse():
    """The attribution/MFU/collective families render as valid exposition
    a Prometheus scraper accepts."""
    m = Metrics()
    m.gauge("train.mfu", 0.187)
    m.gauge("train.flops_per_step", 3.2e12)
    m.gauge("train.collective_ici_bytes_per_step", 204e6)
    m.inc("train.collective_ici_bytes_total", 204e6 * 10)
    for v in (0.01, 0.02):
        m.observe("train.attr.sync_s", v)
    text = render_prometheus(m)
    for line in text.strip().split("\n"):
        assert _LINE.match(line), f"unparseable exposition line: {line!r}"
    assert "# TYPE train_mfu gauge" in text
    assert re.search(r"^train_mfu 0\.187$", text, re.M)
    assert "# HELP train_mfu " in text
    assert "# TYPE train_attr_sync_s histogram" in text
    assert 'train_attr_sync_s_bucket{le="+Inf"} 2' in text
    assert re.search(r"^train_collective_ici_bytes_total 2040000000\.0$",
                     text, re.M)


def test_metrics_server_concurrent_scrape_with_mutation():
    """Scrapes race registry mutation: every scrape must parse (snapshot
    consistency under the lock) and a counter must never move backwards
    between successive scrapes."""
    m = Metrics()
    srv = MetricsServer(m).start()
    stop = threading.Event()
    errors = []

    def mutate(i):
        n = 0
        while not stop.is_set():
            m.inc("scrape_race.counter_total")
            m.gauge(f"scrape_race.gauge_{i}", n)
            m.observe("scrape_race.hist_s", 0.001 * (n % 7 + 1))
            m.add("scrape_race.timer", 0.001)
            n += 1

    threads = [threading.Thread(target=mutate, args=(i,)) for i in range(3)]
    [t.start() for t in threads]
    try:
        last = -1.0
        for _ in range(20):
            with urlreq.urlopen(srv.url, timeout=10) as resp:
                text = resp.read().decode()
            for line in text.strip().split("\n"):
                assert _LINE.match(line), \
                    f"unparseable line under mutation: {line!r}"
            got = re.search(r"^scrape_race_counter_total ([0-9.eE+]+)$",
                            text, re.M)
            if got:
                v = float(got.group(1))
                assert v >= last, "counter moved backwards between scrapes"
                last = v
        assert last > 0, "mutators never landed a counter"
    finally:
        stop.set()
        [t.join(10) for t in threads]
        srv.stop()


def test_metrics_server_scrape():
    m = Metrics()
    m.inc("standalone.scrapes_total")
    srv = MetricsServer(m).start()
    try:
        with urlreq.urlopen(srv.url, timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            body = resp.read().decode()
        assert "standalone_scrapes_total 1.0" in body
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_tracer_span_tree_and_chrome_export(tmp_path):
    t = trace.enable()
    with trace.span("outer", step=7) as outer:
        with trace.span("inner") as inner:
            assert trace.current_span() is inner
            inner.set_attribute("late", "yes")
        assert trace.current_span() is outer
    assert trace.current_span() is None
    spans = {s.name: s for s in t.spans()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["inner"].trace_id == spans["outer"].trace_id
    assert spans["outer"].attrs["step"] == 7
    assert spans["inner"].attrs["late"] == "yes"
    path = t.export_chrome_trace(str(tmp_path / "trace.json"))
    doc = json.load(open(path))
    assert doc["traceEvents"], "chrome trace must contain events"
    for evt in doc["traceEvents"]:
        assert evt["ph"] == "X"
        assert {"name", "ts", "dur", "pid", "tid", "args"} <= set(evt)
    inner_evt = next(e for e in doc["traceEvents"] if e["name"] == "inner")
    assert inner_evt["args"]["parent_id"] == spans["outer"].span_id


def test_tracer_disabled_is_noop():
    trace.disable()
    with trace.span("nothing", a=1) as sp:
        sp.set_attribute("b", 2)
    assert trace.get() is None


def test_tracer_records_exceptions():
    t = trace.enable()
    with pytest.raises(ValueError):
        with trace.span("boom"):
            raise ValueError("no")
    (s,) = t.spans()
    assert "ValueError" in s.attrs["error"]


def test_span_end_exports_before_context_exit():
    # handlers whose LAST write signals completion end the span first,
    # so a reader reacting to that write finds it exported; the context
    # exit then must not double-record or clobber the recorded end time
    t = trace.enable()
    with trace.span("early") as sp:
        sp.end()
        assert trace.current_span() is None
        assert [s.name for s in t.spans()] == ["early"]
        recorded_end = sp.end_s
    assert len(t.spans()) == 1, "context exit double-recorded the span"
    assert sp.end_s == recorded_end
    trace.disable()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_ring_is_bounded(tmp_path):
    rec = FlightRecorder(capacity=8)
    for i in range(20):
        rec.record("evt", i=i)
    events = rec.snapshot()
    assert len(events) == 8
    assert [e["i"] for e in events] == list(range(12, 20))
    assert rec.events_total == 20
    path = rec.dump(str(tmp_path / "fl.jsonl"))
    lines = [json.loads(x) for x in open(path)]
    assert lines[0]["kind"] == "flight_dump"
    assert lines[0]["events"] == 8 and lines[0]["events_total"] == 20
    evts = [x for x in lines if x["kind"] == "evt"]
    assert [x["i"] for x in evts] == list(range(12, 20))


def test_flight_dump_carries_metrics_snapshot(tmp_path):
    """The dump includes final metric state (counters + gauges), so a
    post-mortem shows how far the job got — not just the event ring."""
    global_metrics().inc("obs_test.flight_counter_total", 7)
    global_metrics().gauge("obs_test.flight_gauge", 3.5)
    rec = FlightRecorder(capacity=4)
    rec.record("evt", i=1)
    path = rec.dump(str(tmp_path / "fl2.jsonl"))
    lines = [json.loads(x) for x in open(path)]
    snap = next(x for x in lines if x["kind"] == "metrics_snapshot")
    assert snap["counters"]["obs_test.flight_counter_total"] >= 7
    assert snap["gauges"]["obs_test.flight_gauge"] == 3.5
    # snapshot rides between the header and the event ring
    assert lines[0]["kind"] == "flight_dump"
    assert [x for x in lines if x["kind"] == "evt"]


def test_flight_recorder_signal_dump(tmp_path):
    rec = FlightRecorder(capacity=16, path=str(tmp_path / "sig.jsonl"))
    rec.record("before_signal")
    old = signal.signal(signal.SIGUSR1, lambda *a: None)
    try:
        rec.install(signals=(signal.SIGUSR1,))
        os.kill(os.getpid(), signal.SIGUSR1)
        deadline = time.time() + 5
        while not os.path.exists(rec.path) and time.time() < deadline:
            time.sleep(0.01)
        lines = [json.loads(x) for x in open(rec.path)]
        kinds = [x["kind"] for x in lines]
        assert "before_signal" in kinds and "signal" in kinds
        assert "signal" in lines[0]["reason"]
    finally:
        signal.signal(signal.SIGUSR1, old)


def test_flight_records_injected_fault_and_recovery():
    """Acceptance: under an injected serving fault, the dump shows the
    fault events and the degradation/recovery transitions that followed."""
    faults.install([FaultSpec(point="serving_predict_fail", every=1,
                              max_fires=3)])
    srv = ServingServer(
        InferenceModel(predict_fn=_echo),
        ServingConfig(batch_size=1, batch_timeout_s=0.0,
                      degraded_after_failures=3,
                      degraded_probe_interval_s=0.05)).start()
    try:
        x = np.ones((1, 2), np.float32)
        # three failed batches: the injected fault fires on each, the
        # third flips the server DEGRADED (no fallback -> shedding)
        for _ in range(3):
            rid = srv.enqueue(x)
            with pytest.raises(Exception):
                srv.query(rid, timeout=10)
        deadline = time.time() + 5
        while not srv.degraded and time.time() < deadline:
            time.sleep(0.01)
        assert srv.degraded
        # fault plan exhausted: the half-open probe goes through predict
        # successfully and clears degradation
        out = None
        deadline = time.time() + 10
        while out is None and time.time() < deadline:
            try:
                rid = srv.enqueue(x)
                out = srv.query(rid, timeout=10)
            except Exception:
                time.sleep(0.06)
        assert out is not None and not srv.degraded
    finally:
        srv.stop()
    kinds = [e["kind"] for e in flight.global_recorder().snapshot()]
    assert kinds.count("fault_injected") == 3
    assert "serving_degraded" in kinds
    assert "serving_recovered" in kinds
    assert kinds.index("fault_injected") \
        < kinds.index("serving_degraded") < kinds.index("serving_recovered")


def test_flight_records_breaker_transitions():
    from bigdl_tpu.serving.pool import _Breaker

    b = _Breaker(fail_threshold=2, cooldown_s=0.05, name="worker-9")
    b.record_failure()
    b.record_failure()          # trips open
    assert b.state == "open"
    time.sleep(0.06)
    assert b.try_acquire()      # half-open probe admitted
    b.record_success()          # probe closes it
    kinds = [(e["kind"], e.get("breaker"))
             for e in flight.global_recorder().snapshot()
             if e["kind"].startswith("breaker_")]
    assert kinds == [("breaker_open", "worker-9"),
                     ("breaker_half_open", "worker-9"),
                     ("breaker_closed", "worker-9")]


# ---------------------------------------------------------------------------
# SummaryWriter lifecycle + TFRecord framing
# ---------------------------------------------------------------------------

def test_summary_writer_context_manager_closes_both_sinks(tmp_path):
    with SummaryWriter(str(tmp_path), "train") as sw:
        for i in range(3):
            sw.add_scalar("loss", 1.0 / (i + 1), i)
        tb_path = sw._tb.path
    # exit closed BOTH sinks (the TensorBoard writer's tail events were
    # the bug); close() again is a no-op, not a ValueError
    assert sw._f.closed and sw._tb._f.closed
    sw.close()
    from bigdl_tpu.utils.tbwriter import read_scalars

    recs = read_scalars(tb_path)
    assert [(s, t) for s, t, _ in recs] == [(0, "loss"), (1, "loss"),
                                            (2, "loss")]
    assert sw.read_scalar("loss") == [(0, 1.0), (1, 0.5),
                                      (2, pytest.approx(1 / 3))]


def test_tbwriter_tfrecord_masked_crc_framing(tmp_path):
    """Every record in the event file must carry valid masked-crc32c
    framing — stock TensorBoard silently drops records that don't."""
    from bigdl_tpu.utils import tbwriter

    w = tbwriter.TensorBoardWriter(str(tmp_path))
    w.add_scalar("acc", 0.75, 1)
    w.add_histogram("params", np.arange(100.0), 1)
    w.close()
    data = open(w.path, "rb").read()
    pos, records = 0, 0
    while pos < len(data):
        header = data[pos:pos + 8]
        (length,) = struct.unpack("<Q", header)
        (hcrc,) = struct.unpack_from("<I", data, pos + 8)
        assert hcrc == tbwriter._masked_crc(header)
        payload = data[pos + 12:pos + 12 + length]
        assert len(payload) == length, "truncated record"
        (pcrc,) = struct.unpack_from("<I", data, pos + 12 + length)
        assert pcrc == tbwriter._masked_crc(payload)
        pos += 12 + length + 4
        records += 1
    assert pos == len(data), "trailing garbage after last record"
    assert records == 3  # file_version + scalar + histogram
    # and the known crc32c test vector still holds (Castagnoli, RFC 3720)
    assert tbwriter._crc32c(b"123456789") == 0xE3069283


# ---------------------------------------------------------------------------
# serving integration: /metrics + request-id correlated spans
# ---------------------------------------------------------------------------

def test_frontend_metrics_endpoint_and_request_id():
    """Acceptance: GET /metrics on a running HttpFrontend returns
    Prometheus text containing serving lifecycle counters, mirrored
    training/resilience counters, and histogram bucket lines."""
    # a training-side registry records a recovery; mirroring must make it
    # visible on the serving scrape without sharing the instance
    Metrics().inc("recoveries_total")
    srv = ServingServer(InferenceModel(predict_fn=_echo),
                        ServingConfig(batch_size=4)).start()
    fe = HttpFrontend(srv).start()
    try:
        body = json.dumps(
            {"instances": np.ones((2, 3)).tolist()}).encode()
        req = urlreq.Request(fe.url + "/predict", data=body, headers={
            "Content-Type": "application/json",
            "X-Request-Id": "req-obs-123"})
        with urlreq.urlopen(req, timeout=30) as resp:
            assert resp.headers["X-Request-Id"] == "req-obs-123"
            out = json.loads(resp.read())
        np.testing.assert_allclose(out["predictions"],
                                   np.ones((2, 3)) * 2.0)
        with urlreq.urlopen(fe.url + "/metrics", timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        assert re.search(r"^serving_requests \d", text, re.M)
        assert re.search(r"^recoveries_total \d", text, re.M)
        assert 'serving_latency_s_bucket{le="+Inf"}' in text
        assert re.search(r"^serving_latency_s_count [1-9]", text, re.M)
    finally:
        fe.stop()
        srv.stop()


def test_request_id_header_injection_rejected():
    """A payload-supplied request id is echoed into a RESPONSE header —
    CRLF (and any non-token char) must be rejected with 400, never
    emitted."""
    srv = ServingServer(InferenceModel(predict_fn=_echo),
                        ServingConfig(batch_size=4)).start()
    fe = HttpFrontend(srv).start()
    try:
        for evil in ("x\r\nSet-Cookie: evil=1", "abc\n", "a b", ""):
            body = json.dumps({
                "instances": np.ones((1, 2)).tolist(),
                "request_id": evil}).encode()
            req = urlreq.Request(fe.url + "/predict", data=body, headers={
                "Content-Type": "application/json"})
            try:
                urlreq.urlopen(req, timeout=10)
                assert False, f"expected HTTP 400 for {evil!r}"
            except urlreq.HTTPError as e:  # noqa: F841
                assert e.code == 400, evil
                assert e.headers.get("Set-Cookie") is None
        # a well-formed id still round-trips
        body = json.dumps({"instances": np.ones((1, 2)).tolist(),
                           "request_id": "good-id_1:2.3"}).encode()
        req = urlreq.Request(fe.url + "/predict", data=body, headers={
            "Content-Type": "application/json"})
        with urlreq.urlopen(req, timeout=30) as resp:
            assert resp.headers["X-Request-Id"] == "good-id_1:2.3"
    finally:
        fe.stop()
        srv.stop()


def test_duplicate_inflight_request_id_rejected():
    """A caller-supplied id that duplicates an IN-FLIGHT request must be
    rejected at admission (it keys the result table); a delivered id is
    reusable."""
    import queue as _q

    srv = ServingServer(InferenceModel(predict_fn=_echo),
                        ServingConfig(batch_size=4))
    # not started: the first enqueue stays in flight
    x = np.ones((1, 2), np.float32)
    srv.enqueue(x, request_id="dup-1")
    with pytest.raises(ValueError, match="already in flight"):
        srv.enqueue(x, request_id="dup-1")
    srv.start()
    try:
        out = srv.query("dup-1", timeout=10)
        np.testing.assert_allclose(out, x * 2.0)
        # delivered and queried: the id is free again
        srv.enqueue(x, request_id="dup-1")
        srv.query("dup-1", timeout=10)
        # completed but NEVER fetched (first waiter timed out, or the id
        # reused with a new payload): the stale verdict is discarded and
        # the request recomputes — never a silently-stale answer
        srv.enqueue(x, request_id="dup-2")
        deadline = time.time() + 10
        with srv._result_cv:
            while "dup-2" not in srv._results and time.time() < deadline:
                srv._result_cv.wait(0.1)
        x2 = np.full((1, 2), 3.0, np.float32)
        assert srv.enqueue(x2, request_id="dup-2") == "dup-2"
        np.testing.assert_allclose(srv.query("dup-2", timeout=10), x2 * 2.0)
    finally:
        srv.stop()


def test_chrome_trace_joins_training_and_serving_by_request_id(tmp_path):
    """Acceptance: a short training run plus one served request produce a
    single Chrome-trace JSON whose serving spans carry the request id."""
    from bigdl_tpu import nn, optim
    from bigdl_tpu.data import ArrayDataSet

    t = trace.enable()
    # -- short training run ------------------------------------------------
    x = np.random.RandomState(0).rand(64, 4).astype(np.float32)
    y = (x.sum(-1) > 2).astype(np.int32)
    model = nn.Sequential([nn.Linear(4, 2), nn.LogSoftMax()])
    opt = optim.Optimizer(model, ArrayDataSet(x, y), nn.ClassNLLCriterion(),
                          batch_size=32)
    opt.set_end_when(optim.Trigger.max_iteration(3))
    opt.set_checkpoint(str(tmp_path / "ckpt"),
                       optim.Trigger.max_iteration(2))
    opt.optimize()
    # -- one served request, correlated by X-Request-Id --------------------
    srv = ServingServer(InferenceModel(predict_fn=_echo),
                        ServingConfig(batch_size=4)).start()
    fe = HttpFrontend(srv).start()
    try:
        from bigdl_tpu.serving import HttpClient

        HttpClient(fe.url).predict(np.ones((1, 4)), request_id="trace-rid-1")
    finally:
        fe.stop()
        srv.stop()
    path = t.export_chrome_trace(str(tmp_path / "run.json"))
    doc = json.load(open(path))
    names = [e["name"] for e in doc["traceEvents"]]
    assert names.count("train/step") == 3
    assert "train/dispatch" in names and "train/data" in names
    assert "checkpoint/save" in names
    # every serving phase of THIS request carries its id
    by_rid = [e for e in doc["traceEvents"]
              if e["args"].get("request_id") == "trace-rid-1"
              or "trace-rid-1" in str(e["args"].get("request_ids", ""))]
    got = {e["name"] for e in by_rid}
    assert {"serving/http_request", "serving/enqueue", "serving/batch",
            "serving/predict", "serving/publish"} <= got, got
    # parent links: the engine-side enqueue span nests under the HTTP span
    http_span = next(e for e in doc["traceEvents"]
                     if e["name"] == "serving/http_request")
    enq = next(e for e in doc["traceEvents"]
               if e["name"] == "serving/enqueue")
    assert enq["args"]["parent_id"] == http_span["args"]["span_id"]


def test_profile_dir_wires_iteration_profiler(tmp_path):
    """EngineConfig.profile_dir arms the IterationProfiler for every
    optimize(); training ending INSIDE the trace window still closes it
    (the driver's finally)."""
    from bigdl_tpu import nn, optim
    from bigdl_tpu.data import ArrayDataSet
    from bigdl_tpu.runtime.engine import Engine, EngineConfig, init_engine

    Engine.reset()
    prof_dir = tmp_path / "prof"
    init_engine(EngineConfig(profile_dir=str(prof_dir)))
    x = np.random.RandomState(0).rand(64, 4).astype(np.float32)
    y = (x.sum(-1) > 2).astype(np.int32)
    model = nn.Sequential([nn.Linear(4, 2), nn.LogSoftMax()])
    opt = optim.Optimizer(model, ArrayDataSet(x, y), nn.ClassNLLCriterion(),
                          batch_size=32)
    # window is [10, 15); 12 iterations end mid-window
    opt.set_end_when(optim.Trigger.max_iteration(12))
    opt.optimize()
    assert opt._profiler is not None
    assert opt._profiler.done and not opt._profiler._active
    # the jax.profiler trace actually landed on disk
    assert any(prof_dir.rglob("*")), "no trace files written"


def test_iteration_profiler_context_manager():
    from bigdl_tpu.utils.profiling import IterationProfiler

    with IterationProfiler("/tmp/unused", start_iter=5) as prof:
        pass  # never started a trace window
    assert not prof._active
    prof.close()  # idempotent
