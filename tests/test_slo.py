"""Fleet-wide observability plane (docs/observability.md §Federation /
§SLOs & burn rates / §Decode timelines).

Tier-1 coverage: sliding-window histograms (empty-window NaN vs empty
histogram, rotation under concurrent observe), labeled Prometheus series
+ the collision-safe tenant-label aliases, exposition parse/federate
round-trips, the FEDERATED pool scrape staying well-formed while a worker
is killed mid-scrape (stale series dropped, ``federation_stale``
counted), declarative SLO specs -> multi-window burn rates -> ``slo_burn``
flight events -> the health score the autoscaler consults (chaos spec:
an injected latency violation crosses the burn gauge within one window,
asserted from a single scrape + flight dump), token-level decode
chrome-trace timelines joined by request id, flight dumps carrying the
decode engine's event ring, and cluster-side metric federation
(``cluster.host.*{host=}``)."""

import json
import math
import re
import threading
import time

import numpy as np
import pytest

import jax

from bigdl_tpu.nn.attention import Transformer
from bigdl_tpu.obs import flight, trace
from bigdl_tpu.obs.export import (federate, parse_exposition,
                                  render_prometheus)
from bigdl_tpu.obs.hist import LogHistogram
from bigdl_tpu.obs.slo import SLOEvaluator, SLOSpec, load_specs
from bigdl_tpu.optim.metrics import Metrics, label_key
from bigdl_tpu.serving.http_frontend import HttpClient, HttpFrontend
from bigdl_tpu.serving.pool import ServingPool
from bigdl_tpu.serving.server import ServingConfig, ServingServer

BOS, EOS = 0, 1


@pytest.fixture(autouse=True)
def _clean_obs():
    flight.global_recorder().clear()
    yield
    trace.disable()


class _Model:
    """Minimal predict surface for the continuous engine; ``delay``
    injects the latency violation the SLO chaos specs need."""

    def __init__(self, delay: float = 0.0):
        self.delay = delay

    def predict(self, x):
        if self.delay:
            time.sleep(self.delay)
        return np.asarray(x, np.float32) * 2.0


# a general exposition validator (the test_obs _LINE regex predates
# labels): every line is a comment, a TYPE/HELP header, or a sample with
# an optional label body; each family is declared at most once
_SAMPLE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*(?:\{[^{}]*\})? (?:[0-9.eE+-]+|\+Inf|NaN)$")


def _assert_parse_clean(text: str) -> None:
    types = {}
    for line in text.strip().splitlines():
        if line.startswith("# TYPE "):
            name, typ = line[len("# TYPE "):].split(" ", 1)
            assert name not in types, f"family {name} declared twice"
            types[name] = typ
            continue
        if line.startswith("# HELP ") or line.startswith("#"):
            continue
        assert _SAMPLE.match(line), f"unparseable line: {line!r}"
    # no duplicate series: identical name+labels twice fails a real scrape
    seen = set()
    for line in text.strip().splitlines():
        if line.startswith("#"):
            continue
        series = line.rsplit(" ", 1)[0]
        assert series not in seen, f"duplicate series: {series}"
        seen.add(series)


# ---------------------------------------------------------------------------
# sliding-window histograms
# ---------------------------------------------------------------------------

class TestWindowedHistogram:
    def test_empty_window_nan_while_cumulative_has_data(self):
        """The satellite contract: a stale histogram's WINDOW percentile
        is NaN exactly like an empty histogram's — old samples must not
        masquerade as a fresh p99."""
        t = [0.0]
        h = LogHistogram(window_s=10.0, window_slices=5, clock=lambda: t[0])
        for v in (0.01, 0.02, 0.04):
            h.observe(v)
        assert h.percentile(99) > 0                      # cumulative: data
        assert h.window_percentile(99) > 0               # fresh window too
        t[0] = 100.0                                     # window ages out
        assert math.isnan(h.window_percentile(99))
        assert math.isnan(h.window_fraction_over(0.001))
        assert h.window_count() == 0
        assert h.percentile(99) > 0                      # cumulative keeps
        # and a truly empty histogram answers the same way
        h2 = LogHistogram()
        assert math.isnan(h2.window_percentile(99))
        assert math.isnan(h2.percentile(99))

    def test_window_rotation_tracks_recent_samples_only(self):
        t = [0.0]
        h = LogHistogram(window_s=10.0, window_slices=5, clock=lambda: t[0])
        for _ in range(100):
            h.observe(1.0)       # slow era
        t[0] = 20.0
        for _ in range(100):
            h.observe(0.001)     # fast era — the only one in the window
        assert h.window_percentile(99) <= 0.002
        assert h.percentile(50) >= 0.5 or h.n == 200  # cumulative remembers
        assert h.window_fraction_over(0.5) == 0.0
        # partial ageing: half the window later, old slices drop one by one
        t[0] = 26.0
        h.observe(1.0)
        frac = h.window_fraction_over(0.5)
        assert 0.0 < frac < 0.5

    def test_window_fraction_over_bucket_granularity(self):
        h = LogHistogram()
        for _ in range(90):
            h.observe(0.001)
        for _ in range(10):
            h.observe(10.0)
        assert h.window_fraction_over(1.0) == pytest.approx(0.10)
        assert h.window_fraction_over(100.0) == 0.0

    def test_rotation_under_concurrent_observe(self):
        """The regression spec the satellite asks for: writers observing
        through the shared Metrics registry while a reader rotates the
        window concurrently — nothing lost, nothing double-counted, no
        exception."""
        m = Metrics()
        name = "slo_test.concurrent_latency_s"
        # short window so real rotations happen during the test
        with m._lock:
            m.hists[name] = LogHistogram(window_s=0.2, window_slices=4)
        n_threads, per_thread = 4, 1500
        errors = []

        def write():
            try:
                for i in range(per_thread):
                    m.observe(name, 0.001 * (1 + i % 7))
                    if i % 100 == 0:
                        time.sleep(0.002)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        stop = threading.Event()

        def read():
            try:
                while not stop.is_set():
                    p = m.window_percentile(name, 99)
                    assert math.isnan(p) or p > 0
                    f = m.window_fraction_over(name, 0.004)
                    assert math.isnan(f) or 0.0 <= f <= 1.0
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        writers = [threading.Thread(target=write) for _ in range(n_threads)]
        reader = threading.Thread(target=read)
        reader.start()
        for w in writers:
            w.start()
        for w in writers:
            w.join()
        stop.set()
        reader.join()
        assert not errors
        h = m.hists[name]
        assert h.n == n_threads * per_thread       # nothing lost
        assert sum(h.counts) == h.n                # nothing double-counted
        assert h.window_count() <= h.n


# ---------------------------------------------------------------------------
# labeled series + federation
# ---------------------------------------------------------------------------

class TestLabeledExport:
    def test_labeled_series_share_one_family_declaration(self):
        m = Metrics()
        m.inc("serving.tenant_requests_total", 2, labels={"tenant": "a"})
        m.inc("serving.tenant_requests_total", 5, labels={"tenant": "b"})
        m.observe("serving.tenant_latency_seconds", 0.01,
                  labels={"tenant": "a"})
        m.observe("serving.tenant_latency_seconds", 0.02,
                  labels={"tenant": "b"})
        text = render_prometheus(m)
        _assert_parse_clean(text)
        assert text.count("# TYPE serving_tenant_requests_total") == 1
        assert 'serving_tenant_requests_total{tenant="a"} 2.0' in text
        assert 'serving_tenant_requests_total{tenant="b"} 5.0' in text
        # histogram buckets merge the le label with the series labels
        assert re.search(
            r'serving_tenant_latency_seconds_bucket\{tenant="a",'
            r'le="\+Inf"\} 1', text)
        assert 'serving_tenant_latency_seconds_count{tenant="a"} 1' in text

    def test_label_key_escaping(self):
        assert label_key("n", tenant="a") == 'n{tenant="a"}'
        assert label_key("n", b="2", a="1") == 'n{a="1",b="2"}'
        assert label_key("n", v='x"y\\z') == 'n{v="x\\"y\\\\z"}'

    def test_collision_safety_with_legacy_aliases(self):
        """The satellite's collision spec: legacy name-embedded tenant
        series and the labeled aliases coexist in ONE scrape — distinct
        families, each declared once — while two base names that
        sanitize onto the same family still drop the later one."""
        m = Metrics()
        # the doubled emission the server does per request
        m.observe("serving.tenant.alpha.latency_s", 0.01)
        m.observe("serving.tenant_latency_seconds", 0.01,
                  labels={"tenant": "alpha"})
        # a base-name collision: label form vs a dotted name that
        # sanitizes identically
        m.gauge("serving.tenant_queue_depth", 3.0,
                labels={"tenant": "alpha"})
        m.gauge("serving.tenant.queue_depth", 99.0)
        text = render_prometheus(m)
        _assert_parse_clean(text)
        assert "# TYPE serving_tenant_alpha_latency_s histogram" in text
        assert "# TYPE serving_tenant_latency_seconds histogram" in text
        assert text.count("# TYPE serving_tenant_queue_depth gauge") == 1
        # exactly ONE base name wins the family (sorted order: the dotted
        # name); the loser's sample is dropped, never emitted under a
        # foreign declaration
        assert "serving_tenant_queue_depth 99.0" in text
        assert 'serving_tenant_queue_depth{tenant="alpha"} 3.0' \
            not in text

    def test_parse_exposition_round_trip(self):
        m = Metrics()
        m.inc("a.count", 2)
        m.gauge("b.level", 1.5, labels={"k": "v"})
        m.observe("c.lat_s", 0.1)
        fams = parse_exposition(render_prometheus(m))
        by = {f["name"]: f for f in fams}
        assert by["a_count"]["type"] == "counter"
        assert by["b_level"]["type"] == "gauge"
        assert ("b_level", 'k="v"', "1.5") in by["b_level"]["samples"]
        hist = by["c_lat_s"]
        assert hist["type"] == "histogram"
        assert any(s[0] == "c_lat_s_bucket" for s in hist["samples"])

    def test_federate_injects_labels_and_declares_once(self):
        a, b = Metrics(), Metrics()
        a.inc("serving.requests", 2)
        a.observe("serving.latency_s", 0.1)
        b.inc("serving.requests", 7)
        b.observe("serving.latency_s", 0.2)
        text = federate([({"worker": "w0"}, render_prometheus(a)),
                         ({"worker": "w1"}, render_prometheus(b))])
        _assert_parse_clean(text)
        assert text.count("# TYPE serving_requests counter") == 1
        assert 'serving_requests{worker="w0"} 2.0' in text
        assert 'serving_requests{worker="w1"} 7.0' in text
        # bucket lines keep le= AND gain worker=
        assert re.search(
            r'serving_latency_s_bucket\{le="\+Inf",worker="w1"\} 1', text)


class _FakeWorker:
    """In-process stand-in for a pool worker: routable as long as its
    frontend lives (the federation specs need no subprocesses)."""

    def __init__(self, name, url):
        self.name = name
        self.url = url
        from bigdl_tpu.serving.pool import _Breaker

        self.breaker = _Breaker(name=name)
        self._alive = True

    def alive(self):
        return self._alive

    def routable(self):
        return self._alive and self.url is not None


class TestFederatedPoolScrape:
    @pytest.fixture()
    def pool_of_two(self, request):
        """Two in-process 'workers' (own registries, two tenants each)
        behind a real proxy socket — only the proxy HTTP thread runs; no
        supervisor/autoscaler, no subprocesses."""
        workers, fes = [], []
        for i in range(2):
            srv = ServingServer(
                models={"alpha": _Model(), "beta": _Model()},
                config=ServingConfig(batch_size=4, batch_timeout_s=0.001),
                metrics=Metrics()).start()
            fe = HttpFrontend(srv, port=0).start()
            # traffic on BOTH tenants so per-tenant series exist
            for tenant in ("alpha", "beta"):
                rid = srv.enqueue(np.ones((1, 2), np.float32),
                                  model=tenant)
                srv.query(rid, timeout=10)
            workers.append(srv)
            fes.append(fe)
        pool = ServingPool("unused:loader", workers=0)
        pool.workers = [_FakeWorker(f"worker-{i}", fes[i].url)
                        for i in range(2)]
        t = threading.Thread(target=pool._httpd.serve_forever,
                             daemon=True)
        t.start()

        def fin():
            pool._httpd.shutdown()
            pool._httpd.server_close()
            for fe in fes:
                try:
                    fe.stop()
                except Exception:
                    pass
            for srv in workers:
                srv.stop()

        request.addfinalizer(fin)
        return pool, workers, fes

    def test_federated_scrape_covers_workers_and_tenants(self, pool_of_two):
        """Acceptance: ONE proxy scrape, parse-clean, >=2 live workers
        and >=2 tenants visible via labels."""
        pool, _, _ = pool_of_two
        cl = HttpClient(pool.url)
        text = cl.metrics()
        _assert_parse_clean(text)
        for w in ("worker-0", "worker-1"):
            assert f'worker="{w}"' in text
        # the labeled tenant families carry every tenant on every worker
        for w in ("worker-0", "worker-1"):
            for tenant in ("alpha", "beta"):
                assert re.search(
                    r"serving_tenant_requests_total\{tenant=\"%s\","
                    r"worker=\"%s\"\} 1\.0" % (tenant, w), text), \
                    (tenant, w, text[:2000])
        # proxy-side families ride the same scrape, unlabeled
        assert "# TYPE serving_pool_federation_stale counter" in text \
            or "serving_pool_federation_stale" in text

    def test_worker_killed_mid_scrape_degrades_gracefully(self,
                                                          pool_of_two):
        """Acceptance: killing a worker degrades the scrape (its series
        dropped, federation_stale counted) — the scrape itself stays 200
        and parse-clean.  The operator's dashboard must survive exactly
        the moment workers are dying."""
        pool, workers, fes = pool_of_two
        cl = HttpClient(pool.url)
        before = cl.metrics()
        assert 'worker="worker-1"' in before
        fes[1].stop()          # killed mid-scrape: socket gone, worker
        #                        still listed as routable
        # a real kill severs established sockets too; the in-process
        # frontend only closes its listener, so drop the parked
        # keep-alive conns exactly like the supervisor does on death
        pool.conns.clear(fes[1].url)
        after = cl.metrics()
        _assert_parse_clean(after)
        assert 'worker="worker-0"' in after
        assert 'worker="worker-1"' not in after   # stale series dropped
        assert pool.stats["federation_stale"] >= 1
        # ... and the counter is visible in the very scrape that paid it
        m = re.search(r"serving_pool_federation_stale (\d+)", after)
        assert m and int(m.group(1)) >= 1


# ---------------------------------------------------------------------------
# declarative SLOs
# ---------------------------------------------------------------------------

class TestSLOSpecs:
    def test_spec_grammar(self):
        spec = SLOSpec.from_dict({
            "tenant": "ranker",
            "objectives": {"predict_p99_s": 0.2, "ttft_p95_s": 0.5,
                           "availability": 0.999},
            "window_s": 30.0})
        by = {o.name: o for o in spec.objectives}
        assert by["predict_p99_s"].kind == "latency"
        assert by["predict_p99_s"].target == pytest.approx(0.99)
        assert by["predict_p99_s"].threshold_s == 0.2
        assert by["predict_p99_s"].metric \
            == "serving.tenant_latency_seconds"
        assert by["ttft_p95_s"].metric == "serving.tenant_ttft_seconds"
        assert by["ttft_p95_s"].target == pytest.approx(0.95)
        assert by["availability"].kind == "availability"
        assert by["availability"].budget == pytest.approx(0.001)

    def test_spec_rejects_unknown_objective(self):
        with pytest.raises(ValueError, match="unknown SLO objective"):
            SLOSpec.from_dict({"tenant": "x",
                               "objectives": {"p99_of_vibes": 1}})
        with pytest.raises(ValueError, match="availability target"):
            SLOSpec.from_dict({"tenant": "x",
                               "objectives": {"availability": 1.5}})
        # window_s=0 would busy-spin the background evaluator thread
        with pytest.raises(ValueError, match="window_s"):
            SLOSpec.from_dict({"tenant": "x", "window_s": 0,
                               "objectives": {"predict_p99_s": 0.1}})
        with pytest.raises(ValueError, match="long_window_factor"):
            SLOSpec.from_dict({"tenant": "x", "long_window_factor": 0.5,
                               "objectives": {"predict_p99_s": 0.1}})

    def test_evaluator_presizes_hists_for_long_window(self):
        """A spec window longer than the default 60s ring must be
        answerable: the evaluator pre-sizes its tenant histograms to the
        LONG (6x) window at the short window's slice resolution."""
        m = Metrics()
        SLOEvaluator([{"tenant": "t", "window_s": 60.0,
                       "objectives": {"predict_p99_s": 0.1}}], metrics=m)
        h = m.hists[label_key("serving.tenant_latency_seconds",
                              tenant="t")]
        assert h.window_s == 360.0
        assert h._slice_s == pytest.approx(10.0)  # short window / 6

    def test_load_specs_forms(self, tmp_path):
        d = {"tenant": "a", "objectives": {"predict_p99_s": 0.1}}
        assert len(load_specs([d, dict(d, tenant="b")])) == 2
        assert load_specs(d)[0].tenant == "a"
        assert load_specs(json.dumps([d]))[0].tenant == "a"
        p = tmp_path / "slo.json"
        p.write_text(json.dumps([d]))
        assert load_specs(str(p))[0].tenant == "a"
        assert load_specs(None) == []

    def test_latency_burn_rate_math(self):
        """10% of window samples over a p99 bound = 10x the 1% budget."""
        m = Metrics()
        ev = SLOEvaluator([{"tenant": "t", "window_s": 60.0,
                            "objectives": {"predict_p99_s": 0.1}}],
                          metrics=m)
        for _ in range(90):
            m.observe("serving.tenant_latency_seconds", 0.01,
                      labels={"tenant": "t"})
        for _ in range(10):
            m.observe("serving.tenant_latency_seconds", 1.0,
                      labels={"tenant": "t"})
        (st,) = ev.evaluate()
        assert st.burn == pytest.approx(10.0, rel=0.01)
        assert st.burning
        assert ev.health_score() == 0.0
        g = m.gauges[label_key("slo.burn_rate", tenant="t",
                               objective="predict_p99_s")]
        assert g == pytest.approx(10.0, rel=0.01)

    def test_availability_burn_from_counter_deltas(self):
        t = [0.0]
        m = Metrics()
        ev = SLOEvaluator([{"tenant": "t", "window_s": 10.0,
                            "objectives": {"availability": 0.99}}],
                          metrics=m, clock=lambda: t[0])
        lb = {"tenant": "t"}
        ev.evaluate()                       # baseline counter snapshot
        m.inc("serving.tenant_requests_total", 98, labels=lb)
        m.inc("serving.tenant_failed_total", 2, labels=lb)
        t[0] = 1.0
        (st,) = ev.evaluate()
        assert st.burn == pytest.approx(2.0, rel=0.01)  # 2% bad / 1% budget
        assert st.burning
        # good-only traffic pushes the window ratio back under budget
        m.inc("serving.tenant_requests_total", 900, labels=lb)
        t[0] = 2.0
        (st2,) = ev.evaluate()
        assert st2.burn < st.burn

    def test_no_data_is_no_burn(self):
        m = Metrics()
        ev = SLOEvaluator([{"tenant": "ghost",
                            "objectives": {"predict_p99_s": 0.1,
                                           "availability": 0.999}}],
                          metrics=m)
        for st in ev.evaluate():
            assert st.burn == 0.0 and not st.burning
            assert st.samples == 0
        assert ev.health_score() == 1.0

    def test_burn_flight_event_fires_once_and_clears(self, tmp_path):
        t = [0.0]
        m = Metrics()
        ev = SLOEvaluator([{"tenant": "t", "window_s": 5.0,
                            "objectives": {"predict_p99_s": 0.01}}],
                          metrics=m, clock=lambda: t[0])
        lb = {"tenant": "t"}
        # the histogram shares the injected clock so its window ages on
        # the same timeline the evaluator reads
        with m._lock:
            m.hists[label_key("serving.tenant_latency_seconds", **lb)] \
                = LogHistogram(window_s=5.0, clock=lambda: t[0])
        for _ in range(20):
            m.observe("serving.tenant_latency_seconds", 1.0, labels=lb,
                      )
        ev.evaluate()
        ev.evaluate()          # still burning: no second event
        kinds = [e["kind"] for e in flight.global_recorder().snapshot()]
        assert kinds.count("slo_burn") == 1
        assert m.counters["slo.burn_events_total"] == 1
        # recovery: the window ages out -> burn 0 -> cleared event
        t[0] = 1000.0
        ev.evaluate()
        kinds = [e["kind"] for e in flight.global_recorder().snapshot()]
        assert "slo_burn_cleared" in kinds

    def test_autoscaler_consults_slo_health(self):
        """The pure policy spec: a burning SLO scales up even with empty
        queues, and an unhealthy pool never scales down."""
        dec = ServingPool.autoscale_decision
        base = dict(n_workers=2, min_workers=1, max_workers=4,
                    avg_queue_depth=0.0, up_depth=16.0, idle_ticks=10,
                    down_after=3, breaker_open=False,
                    since_last_scale_s=99.0, cooldown_s=5.0)
        assert dec(**base, slo_health=1.0, unhealthy_below=0.5) == "down"
        assert dec(**base, slo_health=0.2, unhealthy_below=0.5) == "up"
        # cooldown still gates the SLO signal
        assert dec(**dict(base, since_last_scale_s=1.0),
                   slo_health=0.2, unhealthy_below=0.5) == "hold"
        # at the max bound: no up, but ALSO no down while unhealthy
        assert dec(**dict(base, n_workers=4),
                   slo_health=0.2, unhealthy_below=0.5) == "hold"
        # signal disabled (unhealthy_below=0): behaves as before
        assert dec(**base, slo_health=0.0, unhealthy_below=0.0) == "down"


class TestSLOChaosAcceptance:
    def test_injected_latency_fires_burn_within_one_window(self, tmp_path):
        """THE acceptance chaos spec: a forced latency injection drives
        the tenant past its declared SLO — the burn gauge crosses 1.0
        within one evaluation window, an slo_burn flight event lands in
        the dump, and the health score the pool consults reflects it.
        Asserted from a single scrape + a single flight dump."""
        window_s = 5.0
        cfg = ServingConfig(
            batch_size=4, batch_timeout_s=0.001,
            slo=[{"tenant": "default", "window_s": window_s,
                  "objectives": {"predict_p99_s": 0.01,
                                 "availability": 0.99}}])
        srv = ServingServer(_Model(delay=0.05), cfg,
                            metrics=Metrics()).start()
        fe = HttpFrontend(srv, port=0).start()
        try:
            assert srv.slo is not None
            assert srv.slo_health() == 1.0          # before the violation
            t_violation = time.time()
            for _ in range(6):                      # every request 5x over
                rid = srv.enqueue(np.ones((1, 2), np.float32))
                srv.query(rid, timeout=10)
            srv.slo.evaluate()
            detect_s = time.time() - t_violation
            assert detect_s < window_s, \
                "burn must cross within one evaluation window"
            # -- one scrape carries the verdict --------------------------
            text = HttpClient(fe.url).metrics()
            _assert_parse_clean(text)
            m = re.search(
                r'slo_burn_rate\{objective="predict_p99_s",'
                r'tenant="default"\} ([0-9.eE+]+)', text)
            assert m, text[:2000]
            assert float(m.group(1)) > 1.0
            hm = re.search(r"^slo_health ([0-9.eE+-]+)", text, re.M)
            assert hm and float(hm.group(1)) < 0.5
            # the pool's scaling policy acts on exactly this number
            assert ServingPool.autoscale_decision(
                n_workers=1, min_workers=1, max_workers=4,
                avg_queue_depth=0.0, up_depth=16.0, idle_ticks=0,
                down_after=3, breaker_open=False,
                since_last_scale_s=99.0, cooldown_s=5.0,
                slo_health=srv.slo_health(),
                unhealthy_below=0.5) == "up"
            # /health surfaces the same verdict for operators
            health = HttpClient(fe.url).health()
            assert health["slo_health"] < 0.5
            assert health["slo"]["objectives"]
            # -- one flight dump carries the event -----------------------
            path = flight.global_recorder().dump(
                str(tmp_path / "flight.jsonl"))
            events = [json.loads(l) for l in open(path)]
            burns = [e for e in events if e.get("kind") == "slo_burn"]
            assert burns and burns[0]["tenant"] == "default"
            assert burns[0]["objective"] == "predict_p99_s"
            assert burns[0]["burn"] > 1.0
        finally:
            fe.stop()
            srv.stop()


# ---------------------------------------------------------------------------
# token-level decode timelines
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm_served(request):
    from bigdl_tpu.serving import (DecodeConfig, InferenceModel)

    model = Transformer(vocab_size=32, hidden_size=16, num_heads=2,
                        num_layers=2, dropout=0.0, mode="lm")
    v = model.init(jax.random.PRNGKey(0),
                   np.arange(6, dtype=np.int32)[None])
    im = InferenceModel(model, v, decode=DecodeConfig(
        slots=4, page_size=4, pages_per_slot=4, prompt_chunk=4,
        max_new_tokens=8, eos_id=EOS))
    srv = ServingServer(im, ServingConfig(batch_size=4)).start()
    fe = HttpFrontend(srv, port=0).start()

    def fin():
        fe.stop()
        srv.stop()
        im.decode_engine.stop()

    request.addfinalizer(fin)
    return im, srv, fe


class TestDecodeTimelines:
    def test_streamed_generate_chrome_trace_joined_by_request_id(
            self, lm_served, tmp_path):
        """Acceptance: a chrome-trace export of ONE streamed /generate
        request shows admission, each prefill chunk, and per-token steps
        — all joined by its request_id."""
        im, srv, fe = lm_served
        tracer = trace.enable()
        rid = "trace-req-1"
        cl = HttpClient(fe.url)
        events = list(cl.generate([2, 3, 4, 5, 6], temperature=0.0,
                                  stream=True, request_id=rid))
        tokens = [e["token"] for e in events if "token" in e]
        assert events[-1]["done"] is True
        doc = tracer.chrome_trace()
        path = tmp_path / "decode_trace.json"
        tracer.export_chrome_trace(str(path))
        assert json.loads(path.read_text())["traceEvents"]
        mine = [e for e in doc["traceEvents"]
                if e["args"].get("request_id") == rid]
        names = {}
        for e in mine:
            names.setdefault(e["name"], []).append(e)
        # the whole path, one request id: HTTP ingress -> engine submit
        # -> slot admission -> prefill chunks -> per-token steps ->
        # publish
        assert "serving/http_generate" in names
        assert "serving/enqueue_generate" in names
        assert len(names["decode/admission"]) == 1
        # 5-token prompt at prompt_chunk=4 -> exactly 2 prefill chunks
        chunks = sorted(e["args"]["chunk_start"]
                        for e in names["decode/prefill_chunk"])
        assert chunks == [0, 4]
        # every token after the first (which prefill emits) is one step
        steps = names["decode/token_step"]
        assert len(steps) == len(tokens) - 1
        assert sorted(e["args"]["index"] for e in steps) \
            == list(range(1, len(tokens)))
        (pub,) = names["decode/publish"]
        assert pub["args"]["finish_reason"] in ("eos", "length")
        # events are real chrome-trace complete events with wall windows
        for e in mine:
            assert e["ph"] == "X" and e["dur"] >= 0

    def test_tracing_off_is_free_of_decode_events(self, lm_served):
        im, srv, fe = lm_served
        trace.disable()
        rid = srv.enqueue_generate(np.asarray([5, 6], np.int32))
        srv.query(rid)
        assert trace.get() is None      # nothing installed, no cost paid


class TestFlightDumpDecodeRing:
    def test_dump_carries_engine_event_ring(self, lm_served, tmp_path):
        """Satellite: SIGTERM/excepthook dumps include the decode
        engine's event ring (admissions, expiries, prefill interleave)
        next to the metrics_snapshot line — same dump() path the signal
        handlers call."""
        im, srv, fe = lm_served
        rid = srv.enqueue_generate(np.asarray([7, 8, 9], np.int32))
        srv.query(rid)
        path = flight.global_recorder().dump(
            str(tmp_path / "flight.jsonl"))
        lines = [json.loads(l) for l in open(path)]
        kinds = [l["kind"] for l in lines]
        assert "metrics_snapshot" in kinds
        rings = [l for l in lines if l.get("kind") == "dump_source"
                 and "decode_engine" in str(l.get("source"))]
        assert rings, kinds
        ring = rings[-1]
        event_kinds = {e[0] for e in ring["events"]}
        assert "admit" in event_kinds
        assert "prefill_chunk" in event_kinds
        assert ring["stats"]["requests"] >= 1
        # the metrics_snapshot line still precedes the source lines
        assert kinds.index("metrics_snapshot") \
            < kinds.index("dump_source")


# ---------------------------------------------------------------------------
# cluster-side metric federation
# ---------------------------------------------------------------------------

def test_cluster_leader_merges_host_snapshots(tmp_path):
    """Training-side federation: every host publishes its snapshot onto
    the membership board; the LEADER re-exports them as
    cluster.host.*-labeled series, stragglers included via age_s."""
    from bigdl_tpu.resilience.cluster import (ClusterConfig,
                                              ClusterCoordinator)

    d = str(tmp_path / "ctrl")
    t = [100.0]
    mk = lambda rank, m: ClusterCoordinator(
        ClusterConfig(directory=d, process_index=rank,
                      heartbeat_interval_s=5.0, clock=lambda: t[0]),
        metrics=m)
    m0, m1 = Metrics(), Metrics()
    c0, c1 = mk(0, m0), mk(1, m1)
    m1.gauge("train.step_time_max_s", 0.5)
    m1.inc("train.xla_compiles_total", 3)
    m1.observe("serving.tenant_latency_seconds", 0.02,
               labels={"tenant": "x"})
    c0.sweep()          # leader beats first (so rank 1 never leads)
    c1.sweep()          # rank 1 publishes its snapshot, does not merge
    t[0] = 101.0
    c0.sweep()          # leader merges every host file
    text = render_prometheus(m0)
    _assert_parse_clean(text)
    assert 'cluster_host_train_step_time_max_s{host="1"} 0.5' in text
    assert 'cluster_host_train_xla_compiles_total{host="1"} 3.0' in text
    # labeled peer series keep their labels, plus host=
    assert re.search(
        r'cluster_host_serving_tenant_latency_seconds_p99'
        r'\{tenant="x",host="1"\}', text)
    # staleness, not disappearance: the straggler's snapshot ages
    assert re.search(r'cluster_host_age_s\{host="1"\} 1\.0', text)
    m = re.search(r"cluster_hosts_reporting (\d+)", text)
    assert m and int(m.group(1)) == 2          # self included
    # a non-leader never merges: rank 1's registry carries no host series
    assert "cluster_host_" not in render_prometheus(m1).replace(
        "cluster_host_age_s", "")  # (rank1 published, never merged)


def test_cluster_publish_skips_merged_series(tmp_path):
    """The leader's own merged cluster.host.* gauges must not re-publish
    — federation feedback would grow names without bound."""
    from bigdl_tpu.resilience.cluster import (ClusterConfig,
                                              ClusterCoordinator)

    d = str(tmp_path / "ctrl")
    m0 = Metrics()
    c0 = ClusterCoordinator(
        ClusterConfig(directory=d, process_index=0), metrics=m0)
    m0.gauge("train.mfu", 0.2)
    c0.sweep()
    c0.sweep()          # second sweep republishes after a merge happened
    from bigdl_tpu.utils import storage

    doc = storage.read_json(
        storage.join(d, "metrics", "host-r00000.json"))
    assert "train.mfu" in doc["metrics"]
    assert not any(k.startswith("cluster.host") for k in doc["metrics"])


# ---------------------------------------------------------------------------
# knobs
# ---------------------------------------------------------------------------

def test_engine_config_slo_specs_env(monkeypatch):
    from bigdl_tpu.runtime.engine import EngineConfig

    spec = json.dumps([{"tenant": "default",
                        "objectives": {"predict_p99_s": 0.2}}])
    monkeypatch.setenv("BIGDL_TPU_SLO_SPECS", spec)
    cfg = EngineConfig.from_env()
    assert cfg.slo_specs == spec
    assert load_specs(cfg.slo_specs)[0].objectives[0].threshold_s == 0.2


def test_serving_env_slo_specs(monkeypatch):
    spec = json.dumps([{"tenant": "default",
                        "objectives": {"availability": 0.999}}])
    monkeypatch.setenv("BIGDL_TPU_SLO_SPECS", spec)
    srv = ServingServer(_Model(), ServingConfig(slo_alert_burn=2.0),
                        metrics=Metrics())
    assert srv.slo is not None
    assert srv.slo.specs[0].objectives[0].kind == "availability"
    # the configured alert threshold reaches the env-built evaluator too
    assert srv.slo.alert_burn == 2.0
    srv.stop()


@pytest.mark.slow
def test_injected_hard_violation_alerts_inside_one_window():
    """The drill on the real clock: in-budget latencies for a warm-up,
    then every request at 4x the objective's bound; the burn rate crosses
    the alert threshold, and ``slo_burn`` is recorded, inside one window
    of evaluation ticks."""
    window_s, threshold_s, period = 1.0, 0.05, 1.0 / 200.0
    m = Metrics()
    spec = SLOSpec.from_dict({"tenant": "drill",
                              "objectives": {"predict_p99_s": threshold_s},
                              "window_s": window_s})
    interval = window_s / 20.0
    ev = SLOEvaluator([spec], metrics=m, interval_s=interval)
    lb = {"tenant": "drill"}
    t0 = time.time()
    while time.time() - t0 < 0.3:
        m.observe("serving.tenant_latency_seconds", threshold_s / 5,
                  labels=lb)
        ev.maybe_evaluate()
        time.sleep(period)
    assert max(s.burn for s in ev.statuses()) < ev.alert_burn
    inject_t = time.time()
    ticks, burn = 0, 0.0
    while burn < ev.alert_burn and ticks * interval < window_s:
        tick_end = inject_t + (ticks + 1) * interval
        while time.time() < tick_end:
            m.observe("serving.tenant_latency_seconds", threshold_s * 4,
                      labels=lb)
            time.sleep(period)
        ticks += 1
        burn = max(s.burn for s in ev.evaluate())
    assert burn >= ev.alert_burn, "no alert inside one window"
    assert any(e["kind"] == "slo_burn"
               for e in flight.global_recorder().snapshot())
