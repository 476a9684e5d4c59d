"""Live telemetry tests (obs/timeseries.py, slo.py, exemplars.py,
attrib.py + the serving-stack wiring): fake-clock window rollover, the
burn-rate alert state machine driven through every transition, the
exemplar -> trace round-trip via OpenMetrics, top-K sketch accuracy on
a Zipf workload, the disabled-path zero-allocation contract, the
/debug/slo + /debug/hot + /debug/events?since= endpoints, and the
seeded latency-injection acceptance run (flush-p99 SLO ok -> burning
-> ok, visible in /debug/slo, dt_slo_* gauges, and a failing
verdict). Tier-1 safe: in-process servers on ephemeral ports, no TPU.
"""

import json
import random
import threading
import tracemalloc
import urllib.error
import urllib.request
from collections import Counter

import pytest

from diamond_types_tpu.obs import Observability
from diamond_types_tpu.obs.attrib import HotAttribution, SpaceSaving
from diamond_types_tpu.obs.exemplars import ExemplarStore
from diamond_types_tpu.obs.hist import BOUNDS
from diamond_types_tpu.obs.prom import (CONTENT_TYPE,
                                        OPENMETRICS_CONTENT_TYPE,
                                        render_metrics)
from diamond_types_tpu.obs.recorder import FlightRecorder
from diamond_types_tpu.obs.slo import Objective, SloEngine
from diamond_types_tpu.obs.timeseries import TimeSeries, bucket_index

pytestmark = pytest.mark.telemetry


class _Clock:
    """Injectable monotonic clock for deterministic window math."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# ---- windowed time-series ------------------------------------------------

def test_timeseries_rate_and_fake_clock_rollover():
    clk = _Clock()
    ts = TimeSeries(window_s=10.0, n_windows=6, clock=clk)
    for _ in range(30):
        ts.inc("serve.admitted")
    assert ts.rate("serve.admitted", 10.0) == pytest.approx(3.0)
    # a wider horizon spreads the same events over more seconds
    assert ts.rate("serve.admitted", 60.0) == pytest.approx(0.5)
    clk.t = 25.0
    # two windows later the events are out of the 10s horizon but
    # still inside the 60s one
    assert ts.rate("serve.admitted", 10.0) == 0.0
    assert ts.rate("serve.admitted", 60.0) == pytest.approx(0.5)
    # past the whole ring: everything aged out
    clk.t = 65.0
    assert ts.rate("serve.admitted", 60.0) == 0.0
    # ring slot reuse: writing at window index 6 lands in slot 0 and
    # must reset the stale window, not add to it
    ts.inc("serve.admitted", 5)
    assert ts.rate("serve.admitted", 10.0) == pytest.approx(0.5)
    assert ts.recorded == 31


def test_timeseries_hist_rollover_and_quantile_brackets():
    clk = _Clock()
    ts = TimeSeries(window_s=10.0, n_windows=60, clock=clk)
    rng = random.Random(7)
    vals = [rng.choice([1e-5, 1e-4, 1e-3, 1e-2, 0.1])
            * rng.uniform(1.0, 2.0) for _ in range(2000)]
    for v in vals:
        ts.observe("serve.flush", v)
    vals.sort()
    for q in (0.5, 0.9, 0.99):
        true = vals[min(int(q * len(vals)), len(vals) - 1)]
        got = ts.quantile("serve.flush", q, 300.0)
        assert true / 2 <= got <= true * 2, (q, true, got)
    # rate counts hist observations too
    assert ts.rate("serve.flush", 60.0) == pytest.approx(2000 / 60.0)
    # everything rolls out past the horizon
    clk.t = 400.0
    assert ts.quantile("serve.flush", 0.99, 300.0) == 0.0
    assert ts.rate("serve.flush", 300.0) == 0.0


def test_timeseries_count_over_threshold_semantics():
    ts = TimeSeries(window_s=10.0, n_windows=8, clock=_Clock())
    for _ in range(8):
        ts.observe("serve.flush", 0.001)
    for _ in range(2):
        ts.observe("serve.flush", 10.0)
    bad, total = ts.count_over("serve.flush", 0.1, 300.0)
    assert (bad, total) == (2, 10)
    # a value exactly on a bucket bound is GOOD for a threshold on
    # that bound (le is upper-inclusive, matching hist.py)
    ts2 = TimeSeries(window_s=10.0, n_windows=8, clock=_Clock())
    b = BOUNDS[10]
    ts2.observe("x", b)
    assert ts2.count_over("x", b, 300.0) == (0, 1)
    assert bucket_index(b) == 10
    # sum_over folds counters and latency sums
    ts2.inc("y", 4.0)
    assert ts2.sum_over("y", 300.0) == pytest.approx(4.0)
    assert ts2.sum_over("x", 300.0) == pytest.approx(b)


def test_timeseries_snapshot_shape():
    ts = TimeSeries(window_s=10.0, n_windows=8, clock=_Clock())
    ts.inc("serve.admitted", 6)
    ts.observe("serve.flush", 0.02)
    snap = ts.snapshot()
    assert snap["version"] == 1 and snap["enabled"]
    assert snap["recorded"] == 2
    row = snap["series"]["serve.admitted"]
    assert row["rate_60s"] == pytest.approx(0.1)
    assert snap["series"]["serve.flush"]["p99_300s"] > 0
    json.dumps(snap)   # JSON-able for /metrics


# ---- zero-allocation disabled paths --------------------------------------

def test_disabled_telemetry_single_branch_zero_alloc():
    """The disabled live tier is ONE branch per call: tracemalloc must
    attribute zero allocations to timeseries/exemplars/attrib across
    200 record cycles (mirrors the obs/trace.py pin)."""
    import diamond_types_tpu.obs.attrib as at_mod
    import diamond_types_tpu.obs.exemplars as ex_mod
    import diamond_types_tpu.obs.timeseries as ts_mod
    ts = TimeSeries(enabled=False)
    ex = ExemplarStore(enabled=False)
    at = HotAttribution(enabled=False)
    # touch everything once before measuring
    ts.inc("w")
    ts.observe("w", 0.1)
    ex.note("w", 0.1, "ab")
    at.note("ops", doc="d", agent="a")
    files = {ts_mod.__file__, ex_mod.__file__, at_mod.__file__}

    def _cycle():
        for _ in range(200):
            ts.inc("serve.admitted")
            ts.observe("serve.flush", 0.01)
            ex.note("serve.flush", 0.01, "abcd")
            at.note("ops", doc="d1", agent="a1")

    # Interpreter artifacts can masquerade as growth: function-entry
    # frame objects are occasionally malloc'd fresh (empty freelist) and
    # attributed to the `def` line of these files, and lineno-0 rows are
    # module bookkeeping. Warm one full loop, filter to real source
    # lines, and retry a bounded number of times — a genuine per-call
    # leak in the disabled path fails every attempt with count ~200.
    _cycle()
    grew = []
    tracemalloc.start()
    for _attempt in range(3):
        before = tracemalloc.take_snapshot()
        _cycle()
        after = tracemalloc.take_snapshot()
        grew = [st for st in after.compare_to(before, "lineno")
                if st.size_diff > 0
                and st.traceback[0].filename in files
                and st.traceback[0].lineno > 0]
        if not grew:
            break
    tracemalloc.stop()
    assert not grew, [str(g) for g in grew]
    assert ts.recorded == 0 and ex.noted == 0 and at.noted == 0


def test_observability_telemetry_toggle():
    """`telemetry=False` (the bench A/B control arm) disables the live
    tier while the cumulative tier keeps working, and the SLO verdict
    trivially passes."""
    obs = Observability(sample_rate=1.0, telemetry=False)
    assert not obs.ts.enabled
    assert not obs.exemplars.enabled and not obs.attrib.enabled
    obs.ts.observe("serve.flush", 99.0)
    v = obs.slo.verdict()
    assert v["slo_ok"] and not v["burning"]
    snap = obs.snapshot()
    assert snap["timeseries"]["enabled"] is False
    assert snap["slo"]["enabled"] is False
    # the cumulative tier is untouched by the toggle
    obs.tracer.start("t").end()
    assert obs.tracer.stats()["started"] >= 1


# ---- burn-rate state machine ---------------------------------------------

def _tight_objective(**kw):
    base = dict(name="flush_p99", series="serve.flush",
                threshold_s=0.1, target=0.99,
                fast_window_s=60.0, slow_window_s=300.0)
    base.update(kw)
    return Objective(**base)


def test_burn_rate_transition_matrix():
    """ok -> warning -> burning -> ok through seeded latencies on a
    fake clock, with every transition recorded for /debug/events."""
    clk = _Clock()
    ts = TimeSeries(window_s=10.0, n_windows=60, clock=clk)
    rec = FlightRecorder(capacity=32)
    eng = SloEngine(ts, objectives=[_tight_objective()], recorder=rec)

    def state():
        return eng.evaluate()[0]["state"]

    # ok: plenty of traffic, all under threshold
    for _ in range(100):
        ts.observe("serve.flush", 0.005)
    assert state() == "ok"
    # warning: ~2% bad -> burn ~2 (>= 1.0) on both horizons, but the
    # fast page threshold (14.4) is not met
    for _ in range(2):
        ts.observe("serve.flush", 1.0)
    assert state() == "warning"
    # burning: ~23% bad -> fast burn ~23 >= 14.4 AND slow ~23 >= 6
    for _ in range(28):
        ts.observe("serve.flush", 1.0)
    assert state() == "burning"
    # back to ok once the bad windows age past the slow horizon
    clk.t = 400.0
    for _ in range(50):
        ts.observe("serve.flush", 0.005)
    assert state() == "ok"
    al = eng.snapshot()
    assert al["objectives"][0]["transitions"] == 3
    kinds = [e for e in rec.dump() if e["kind"] == "slo_transition"]
    assert [(e["frm"], e["to"]) for e in kinds] == \
        [("ok", "warning"), ("warning", "burning"), ("burning", "ok")]


def test_burn_rate_fast_blip_without_slow_budget_is_warning():
    """The fast AND slow conjunction suppresses one-window blips: a
    100%-bad fast window over a mostly-good slow horizon pages
    nothing."""
    clk = _Clock()
    ts = TimeSeries(window_s=10.0, n_windows=60, clock=clk)
    eng = SloEngine(ts, objectives=[_tight_objective()])
    for _ in range(400):                      # good history at t=0
        ts.observe("serve.flush", 0.005)
    clk.t = 250.0                             # inside slow, past fast
    for _ in range(20):                       # a fully-bad fast window
        ts.observe("serve.flush", 1.0)
    row = eng.evaluate()[0]
    assert row["fast"]["burn"] >= 14.4
    assert row["slow"]["burn"] < 6.0
    assert row["state"] == "warning"


def test_slo_empty_series_is_ok_and_verdict_shape():
    eng = SloEngine(TimeSeries(clock=_Clock()))
    snap = eng.snapshot()
    assert snap["ok"] and snap["by_state"]["burning"] == 0
    assert {r["state"] for r in snap["objectives"]} == {"ok"}
    v = eng.verdict()
    assert v == {"slo_ok": True, "burning": [], "warning": []}


# ---- exemplars -----------------------------------------------------------

def test_exemplar_trace_roundtrip_openmetrics():
    """An exemplar noted against a sampled span must come back out of
    the OpenMetrics exposition on the right `le` bucket line, carrying
    a trace id that resolves to a buffered span."""
    from diamond_types_tpu.serve.metrics import ServeMetrics
    obs = Observability(sample_rate=1.0)
    sm = ServeMetrics(2, flush_docs=4, max_pending=64)
    sm.ts = obs.ts
    span = obs.tracer.start("serve.flush")
    tid = span.context().trace_id
    dur = 0.003
    sm.record_flush(0, 2, 5, "size", dur_s=dur)
    obs.exemplars.note("serve.flush", dur, tid)
    span.end()
    # store-level round trip
    fam = obs.exemplars.for_family("serve.flush")
    le = BOUNDS[bucket_index(dur)]
    assert fam[le]["trace"] == tid
    assert fam[le]["value"] == pytest.approx(dur)
    # exposition round trip (OM only)
    doc = {"serve": sm.snapshot(), "obs": obs.snapshot()}
    om = render_metrics(doc, openmetrics=True)
    lines = [ln for ln in om.splitlines()
             if ln.startswith("dt_flush_latency_seconds_bucket")
             and f'trace_id="{tid}"' in ln]
    assert len(lines) == 1, om
    assert f'le="{le!r}"' in lines[0]
    assert om.rstrip().endswith("# EOF")
    # OM counter TYPE lines drop _total; samples keep it
    for ln in om.splitlines():
        if ln.startswith("# TYPE") and ln.endswith(" counter"):
            assert not ln.split()[2].endswith("_total"), ln
    assert "dt_serve_flushed_ops_total 5" in om
    # classic exposition: no exemplars, no EOF, _total TYPEs intact
    classic = render_metrics(doc)
    assert "trace_id=" not in classic
    assert "# EOF" not in classic
    assert "# TYPE dt_serve_flushed_ops_total counter" in classic
    # the trace id resolves to a real buffered span
    assert tid in {s["trace"] for s in obs.tracer.spans()}


def test_exemplar_overflow_bucket_is_inf():
    ex = ExemplarStore()
    ex.note("serve.flush", 1e9, "aa")        # beyond the last bound
    snap = ex.snapshot()
    assert snap["families"]["serve.flush"][0]["le"] == "+Inf"
    assert snap["noted"] == 1


# ---- top-K attribution ---------------------------------------------------

def test_space_saving_vs_exact_on_zipf():
    """Sketch guarantees on a Zipf workload: every key with true count
    > total/k is tracked, and every reported count brackets truth
    within its error bound."""
    rng = random.Random(42)
    n_keys, n_events, k = 500, 20000, 64
    weights = [1.0 / (i + 1) ** 1.2 for i in range(n_keys)]
    events = rng.choices(range(n_keys), weights=weights, k=n_events)
    sk = SpaceSaving(k)
    exact = Counter()
    for e in events:
        key = f"doc{e:03d}"
        sk.offer(key)
        exact[key] += 1
    assert sk.total == n_events
    assert len(sk.counts) == k
    for key, true in exact.items():
        if true > n_events / k:
            assert key in sk.counts, key
    for key, cnt, err in sk.top(10):
        true = exact[key]
        assert true <= cnt <= true + err + 1e-9, (key, true, cnt, err)
    # the true heavy hitters rank at the top
    reported = [key for key, _, _ in sk.top(10)]
    for key, _ in exact.most_common(3):
        assert key in reported


def test_hot_attribution_dims_kinds_and_prom():
    at = HotAttribution(k=8)
    at.note("ops", doc="d1", agent="alice", n=5)
    at.note("ops", doc="d2", n=1)
    at.note("bytes", doc="d1", n=1024)
    at.note("device_s", doc="d1", n=0.25)
    at.note("cache_misses", doc="d2")
    at.note("ops", n=3)          # no doc/agent: counted nowhere
    snap = at.snapshot(top=5)
    assert snap["doc"]["ops"]["top"][0][0] == "d1"
    assert snap["doc"]["bytes"]["total"] == pytest.approx(1024)
    assert snap["agent"]["ops"]["top"][0][:2] == ["alice", 5]
    assert snap["doc"]["cache_misses"]["tracked"] == 1
    text = render_metrics({"obs": {"hot": snap}})
    assert ('dt_hot_top{dim="doc",key="d1",kind="ops"} 5' in text)
    assert ('dt_hot_attributed_total{dim="doc",kind="bytes"} 1024'
            in text)


# ---- double-write choke points -------------------------------------------

def test_metrics_double_write_into_timeseries():
    """Every record_* choke point in serve/read/replicate metrics
    lands its live twin in the shared TimeSeries under the canonical
    family names the SLO objectives read."""
    from diamond_types_tpu.read.metrics import ReadMetrics
    from diamond_types_tpu.replicate.metrics import ReplicationMetrics
    from diamond_types_tpu.serve.metrics import ServeMetrics
    ts = TimeSeries(clock=_Clock())
    sm = ServeMetrics(2, flush_docs=4, max_pending=64)
    sm.ts = ts
    sm.bump(0, "submits")
    sm.record_flush(0, 2, 5, "size", dur_s=0.003)
    sm.observe_queue_wait(0.02)
    sm.record_hydration("prefetches")
    sm.observe_cold_start(0.01)
    rm = ReadMetrics()
    rm.ts = ts
    rm.bump("reads")
    rm.observe_staleness(0.1)
    rm.observe_wait(0.01)
    pm = ReplicationMetrics()
    pm.ts = ts
    pm.bump("quorum", "acks", 3)
    pm.observe_latency("quorum_round", 0.2)
    want = {"serve.submits", "serve.flush", "serve.flushed_ops",
            "serve.queue_wait", "serve.hydration.prefetches",
            "serve.hydration_cold_start", "read.reads",
            "read.staleness", "read.read_wait", "repl.quorum.acks",
            "repl.quorum_round"}
    assert want <= set(ts.names())
    # the SLO objective series specifically
    assert ts.count_over("serve.flush", 30.0, 300.0) == (0, 1)
    assert ts.quantile("serve.queue_wait", 0.99, 300.0) > 0
    # the cumulative tier recorded too (double-write, not a move)
    snap = sm.snapshot()
    assert snap["latencies"]["queue_wait"]["count"] == 1
    assert snap["version"] == ServeMetrics.SCHEMA_VERSION


# ---- zero-fill satellite -------------------------------------------------

def test_prom_zero_fills_read_and_hydration_families():
    """A fresh server with zero traffic (and no read tier at all)
    still exposes the full dt_read_* / dt_serve_hydration_* families
    so dashboards never see series flicker into existence."""
    from diamond_types_tpu.read.metrics import READ_KEYS
    from diamond_types_tpu.serve.metrics import HYDRATION_KEYS, \
        ServeMetrics
    sm = ServeMetrics(2, flush_docs=4, max_pending=64)
    text = render_metrics({"serve": sm.snapshot()})
    for key in READ_KEYS:
        assert f"dt_read_{key}_total 0" in text, key
    for key in HYDRATION_KEYS:
        assert f"dt_serve_hydration_{key}_total 0" in text, key
    assert "dt_read_local_ratio 0.0" in text
    assert "dt_read_staleness_seconds_count 0" in text
    assert "dt_read_wait_latency_seconds_count 0" in text
    assert "dt_queue_wait_latency_seconds_count 0" in text


# ---- server endpoints ----------------------------------------------------

def _serve_one(**obs_opts):
    from diamond_types_tpu.tools.server import serve
    opts = {"sample_rate": 0.0}
    opts.update(obs_opts)
    httpd = serve(port=0, obs_opts=opts)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, addr


def _get_json(addr, path):
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=5) as r:
        return json.loads(r.read())


def test_debug_events_since_cursor():
    httpd, addr = _serve_one()
    try:
        rec = httpd.store.obs.recorder
        rec.record("ev_a", i=1)
        rec.record("ev_b", i=2)
        full = _get_json(addr, "/debug/events")
        assert len(full["events"]) == 2
        cursor = full["events"][-1]["seq"]
        inc = _get_json(addr, f"/debug/events?since={cursor}")
        assert inc["events"] == [] and inc["since"] == cursor
        rec.record("ev_c", i=3)
        inc = _get_json(addr, f"/debug/events?since={cursor}")
        assert [e["kind"] for e in inc["events"]] == ["ev_c"]
        assert inc["events"][0]["seq"] > cursor
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get_json(addr, "/debug/events?since=nope")
        assert ei.value.code == 400
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_openmetrics_content_negotiation():
    httpd, addr = _serve_one()
    try:
        # ?format=openmetrics forces OM 1.0
        with urllib.request.urlopen(
                f"http://{addr}/metrics?format=openmetrics",
                timeout=5) as r:
            assert r.headers["Content-Type"] == OPENMETRICS_CONTENT_TYPE
            assert r.headers["Cache-Control"] == "no-store"
            text = r.read().decode("utf8")
        assert text.rstrip().endswith("# EOF")
        # ?format=prom + an OpenMetrics Accept header negotiates up
        req = urllib.request.Request(
            f"http://{addr}/metrics?format=prom",
            headers={"Accept":
                     "application/openmetrics-text; version=1.0.0"})
        with urllib.request.urlopen(req, timeout=5) as r:
            assert r.headers["Content-Type"] == OPENMETRICS_CONTENT_TYPE
            assert r.read().decode("utf8").rstrip().endswith("# EOF")
        # plain ?format=prom stays classic (no EOF, classic ctype)
        with urllib.request.urlopen(
                f"http://{addr}/metrics?format=prom", timeout=5) as r:
            assert r.headers["Content-Type"] == CONTENT_TYPE
            assert "# EOF" not in r.read().decode("utf8")
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_slo_latency_injection_ok_burning_ok():
    """Acceptance: seeded latency injection drives the flush-p99 SLO
    ok -> burning -> ok, visible in GET /debug/slo, the dt_slo_*
    gauges, and a failing verdict (the block serve-bench and the soaks
    embed)."""
    httpd, addr = _serve_one(
        objectives=[_tight_objective()],
        ts_window_s=10.0, ts_windows=60)
    try:
        obs = httpd.store.obs
        clk = _Clock()
        obs.ts._clock = clk      # deterministic rollover
        obs.ts._t0 = 0.0
        # phase 1: healthy flush latencies -> ok everywhere
        for _ in range(200):
            obs.ts.observe("serve.flush", 0.005)
        snap = _get_json(addr, "/debug/slo")
        assert snap["ok"] is True
        assert snap["objectives"][0]["state"] == "ok"
        assert obs.slo.verdict()["slo_ok"] is True
        # phase 2: inject slow flushes -> burning, failing verdict
        for _ in range(60):
            obs.ts.observe("serve.flush", 1.0)
        snap = _get_json(addr, "/debug/slo")
        assert snap["ok"] is False
        row = snap["objectives"][0]
        assert row["state"] == "burning"
        assert row["fast"]["burn"] >= row["fast_burn_threshold"]
        with urllib.request.urlopen(
                f"http://{addr}/metrics?format=prom", timeout=5) as r:
            text = r.read().decode("utf8")
        assert 'dt_slo_state{objective="flush_p99"} 2' in text
        assert "dt_slo_ok 0" in text
        assert 'dt_slo_burn_rate{objective="flush_p99",window="fast"}' \
            in text
        v = obs.slo.verdict()
        assert v["slo_ok"] is False and v["burning"] == ["flush_p99"]
        # phase 3: the injected windows age out past the slow horizon
        clk.t = 400.0
        for _ in range(100):
            obs.ts.observe("serve.flush", 0.005)
        snap = _get_json(addr, "/debug/slo")
        assert snap["ok"] is True
        assert snap["objectives"][0]["state"] == "ok"
        assert snap["objectives"][0]["transitions"] >= 2
        # every transition hit the flight recorder for ?since= tails
        ev = _get_json(addr, "/debug/events")
        kinds = [e["to"] for e in ev["events"]
                 if e["kind"] == "slo_transition"]
        assert "burning" in kinds and "ok" in kinds
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_debug_hot_endpoint_and_obs_watch_cli(capsys):
    httpd, addr = _serve_one()
    try:
        obs = httpd.store.obs
        for _ in range(5):
            obs.attrib.note("ops", doc="hotdoc", agent="alice")
        obs.attrib.note("bytes", doc="hotdoc", n=2048)
        hot = _get_json(addr, "/debug/hot")
        assert hot["doc"]["ops"]["top"][0][0] == "hotdoc"
        assert hot["agent"]["ops"]["top"][0][0] == "alice"
        obs.ts.observe("serve.flush", 0.01)
        # the obs-watch CLI renders one round and exits 0 while no
        # objective burns
        from diamond_types_tpu.tools import cli
        rc = cli.main(["obs-watch", addr, "--rounds", "1",
                       "--interval", "0"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "== slo ==" in out and "== hot docs ==" in out
        assert "hotdoc" in out
        assert "flush_p99" in out
    finally:
        httpd.shutdown()
        httpd.server_close()
