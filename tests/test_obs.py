"""Observability tests (diamond_types_tpu/obs/): histogram math vs.
brute force, trace-context propagation across a proxied write, the
flight recorder's bounded ring, Prometheus rendering validity, and the
disabled-path zero-allocation contract. Tier-1 safe: in-process
servers on ephemeral ports, no TPU."""

import json
import random
import re
import threading
import time
import tracemalloc
import urllib.request

import pytest

from diamond_types_tpu.obs import Observability
from diamond_types_tpu.obs.hist import BOUNDS, Histogram, HistogramSet
from diamond_types_tpu.obs.prom import (CONTENT_TYPE, escape_label_value,
                                        render_metrics)
from diamond_types_tpu.obs.recorder import FlightRecorder
from diamond_types_tpu.obs.trace import (NOOP_SPAN, TRACE_HEADER, Tracer,
                                         format_context, parse_header)
from diamond_types_tpu.serve.metrics import ServeMetrics

pytestmark = pytest.mark.obs


# ---- histograms ----------------------------------------------------------

def test_histogram_counts_sum_max_exact():
    rng = random.Random(11)
    vals = [rng.uniform(1e-7, 5.0) for _ in range(500)]
    h = Histogram()
    for v in vals:
        h.record(v)
    s = h.snapshot()
    assert s["count"] == len(vals)
    assert s["sum"] == pytest.approx(sum(vals))
    assert s["max"] == pytest.approx(max(vals))


def test_histogram_quantiles_vs_bruteforce():
    """Log2 buckets bound the quantile error: the reported value must
    bracket the true quantile within one bucket (a factor of 2)."""
    rng = random.Random(7)
    # mixed scales, like real latencies: µs bookkeeping to 100ms flushes
    vals = [rng.choice([1e-6, 1e-5, 1e-4, 1e-3, 1e-2, 0.1])
            * rng.uniform(1.0, 2.0) for _ in range(2000)]
    h = Histogram()
    for v in vals:
        h.record(v)
    vals.sort()
    for q in (0.5, 0.9, 0.99):
        true = vals[min(int(q * len(vals)), len(vals) - 1)]
        got = h.quantile(q)
        assert true / 2 <= got <= true * 2, (q, true, got)
    s = h.snapshot()
    assert s["p50"] <= s["p90"] <= s["p99"]


def test_histogram_bucket_upper_inclusive():
    """Prometheus le semantics: a value exactly on a bucket bound is
    counted by that bound's cumulative bucket."""
    h = Histogram()
    for b in BOUNDS[:6]:
        h.record(b)
    buckets = dict()
    for le, cum in h.snapshot()["buckets"]:
        buckets[le] = cum
    for i, b in enumerate(BOUNDS[:6]):
        assert buckets[b] == i + 1, (b, buckets)


def test_histogram_empty_and_overflow():
    h = Histogram()
    s = h.snapshot()
    assert s["count"] == 0 and s["p99"] == 0.0
    h.record(1e9)   # beyond the last bound -> overflow bucket
    s = h.snapshot()
    assert s["count"] == 1
    assert s["buckets"][-1] == ["+Inf", 1] or \
        tuple(s["buckets"][-1]) == ("+Inf", 1)


def test_histogram_set_label_grouping():
    hs = HistogramSet()
    hs.observe("http_request", 0.01, endpoint="edit", method="POST")
    hs.observe("http_request", 0.02, endpoint="edit", method="POST")
    hs.observe("http_request", 0.03, endpoint="state", method="GET")
    snap = hs.snapshot()
    rows = snap["http_request"]
    by_ep = {r["labels"]["endpoint"]: r for r in rows}
    assert by_ep["edit"]["count"] == 2
    assert by_ep["state"]["count"] == 1


# ---- flight recorder -----------------------------------------------------

def test_recorder_bounded_and_ordered():
    r = FlightRecorder(capacity=8)
    for i in range(20):
        r.record("ev", i=i)
    dump = r.dump()
    assert len(dump) == 8
    seqs = [e["seq"] for e in dump]
    assert seqs == sorted(seqs)           # oldest-first
    assert [e["i"] for e in dump] == list(range(12, 20))  # last 8 kept
    st = r.stats()
    assert st["recorded"] == 20
    assert st["buffered"] == 8
    assert st["dropped"] == 12
    assert r.tail(3) == dump[-3:]


def test_recorder_disabled_is_noop():
    r = FlightRecorder(capacity=8, enabled=False)
    for i in range(5):
        r.record("ev", i=i)
    assert r.dump() == []
    assert r.stats()["recorded"] == 0


# ---- trace context -------------------------------------------------------

def test_trace_header_roundtrip():
    tr = Tracer(sample_rate=1.0, seed=1)
    span = tr.start("root")
    hdr = span.header()
    ctx = parse_header(hdr)
    assert ctx is not None
    assert ctx.trace_id == span.context().trace_id
    assert ctx.span_id == span.context().span_id
    assert ctx.sampled
    assert format_context(ctx) == hdr
    span.end()


def test_trace_header_malformed_rejected():
    for bad in ("", "x", "ab-cd", "zz-11-1", "a-b-1-extra",
                "f" * 33 + "-11-1", "11-" + "f" * 33 + "-1", None):
        assert parse_header(bad) is None
    # any flags value other than "1" is valid-but-unsampled, not junk
    ctx = parse_header("ab-cd-2")
    assert ctx is not None and not ctx.sampled


def test_parent_sampling_inherited():
    tr = Tracer(sample_rate=0.0, seed=1)   # head-samples nothing...
    root = tr.start("r")
    assert root is NOOP_SPAN
    # ...but a sampled incoming context forces the continuation
    ctx = parse_header("00000000000000aa-00000000000000bb-1")
    child = tr.start("c", parent=ctx)
    assert child.sampled
    assert child.context().trace_id == ctx.trace_id
    child.end()
    # and an unsampled parent pins the whole subtree out
    unsampled = parse_header("00000000000000aa-00000000000000bb-0")
    assert tr.start("c2", parent=unsampled) is NOOP_SPAN


def test_disabled_tracer_single_branch_zero_alloc():
    """The disabled path is ONE branch returning the NOOP singleton —
    pinned by identity and by tracemalloc showing zero allocations
    attributed to obs/trace.py across 200 start/annotate/end cycles."""
    tr = Tracer(enabled=False)
    assert tr.start("x") is NOOP_SPAN
    assert tr.start("x", force=True) is NOOP_SPAN
    import diamond_types_tpu.obs.trace as trace_mod
    tr.start("warmup").end()   # touch everything once before measuring
    tracemalloc.start()
    before = tracemalloc.take_snapshot()
    for _ in range(200):
        sp = tr.start("x")
        sp.annotate(k=1)
        sp.end()
    after = tracemalloc.take_snapshot()
    tracemalloc.stop()
    grew = [st for st in after.compare_to(before, "filename")
            if st.size_diff > 0
            and st.traceback[0].filename == trace_mod.__file__]
    assert not grew, [str(g) for g in grew]


# ---- Prometheus rendering ------------------------------------------------

def test_escape_label_value():
    assert escape_label_value('a"b\\c\nd') == 'a\\"b\\\\c\\nd'


_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[a-zA-Z_][a-zA-Z0-9_]*="'
    r'(\\.|[^"\\])*"(,[a-zA-Z_][a-zA-Z0-9_]*="(\\.|[^"\\])*")*\})?'
    r' -?([0-9.e+-]+|\+Inf|NaN)$')


def _check_prom(text: str) -> None:
    """Shape check: every line is a comment or a valid sample, one
    # TYPE per family, no duplicate (name, labels) sample."""
    seen_types = set()
    seen_samples = set()
    for line in text.strip().splitlines():
        if line.startswith("# TYPE"):
            fam = line.split()[2]
            assert fam not in seen_types, f"duplicate TYPE {fam}"
            seen_types.add(fam)
            continue
        if line.startswith("#"):
            continue
        assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"
        key = line.rsplit(" ", 1)[0]
        assert key not in seen_samples, f"duplicate sample {key}"
        seen_samples.add(key)


def test_prom_renderer_from_live_snapshots():
    from diamond_types_tpu.replicate.metrics import ReplicationMetrics
    from diamond_types_tpu.serve.metrics import ServeMetrics
    sm = ServeMetrics(2, flush_docs=4, max_pending=64)
    sm.record_flush(0, 2, 5, "size", dur_s=0.003)
    sm.observe_queue(1, 3)
    rm = ReplicationMetrics()
    rm.bump("quorum", "acks", 3)
    rm.observe_handoff_latency(0.25)
    rm.observe_latency("probe", 0.001)
    obs = Observability(sample_rate=1.0)
    obs.tracer.start("t").end()
    # label values that need escaping must survive the renderer
    obs.hist.observe("http_request", 0.01, endpoint='we"ird\\pa\nth',
                     method="GET")
    obs.recorder.record("circuit_open", peer="p1")
    doc = {"serve": sm.snapshot(), "replication": rm.snapshot(),
           "obs": obs.snapshot()}
    text = render_metrics(doc)
    _check_prom(text)
    assert "dt_flush_latency_seconds_count 1" in text
    assert "dt_handoff_latency_seconds_count 1" in text
    assert 'we\\"ird\\\\pa\\nth' in text
    assert "dt_repl_quorum_acks_total 3" in text


def test_prom_renderer_handles_missing_sections():
    _check_prom(render_metrics({"serve": None, "replication": None}))


def test_prom_renders_witness_and_lint_families():
    """The concurrency-invariant tier exports through the same one-
    TYPE-per-name builder: dt_witness_* from the runtime lock witness,
    dt_lint_violations_total{rule} from the last published dt-lint
    report (zero-filled per rule on a clean run)."""
    from diamond_types_tpu.analysis import (make_lock, witness_disable,
                                            witness_enable,
                                            witness_reset)
    from diamond_types_tpu.analysis.lint import SEVERITY, publish_report
    witness_reset()
    witness_enable()
    try:
        outer = make_lock("t.outer", "global")
        inner = make_lock("t.inner", "shard")
        with outer:
            with inner:
                pass
    finally:
        witness_disable()
    publish_report({"files": 3, "by_rule": {r: 0 for r in SEVERITY},
                    "errors": 0, "warnings": 0, "ok": True})
    obs = Observability(enabled=False)
    text = render_metrics({"obs": obs.snapshot()})
    _check_prom(text)
    assert 'dt_witness_edges{edge="global->shard"} 1' in text
    assert "dt_witness_acyclic 1" in text
    assert "dt_witness_violations_total 0" in text
    for rule in SEVERITY:
        assert f'dt_lint_violations_total{{rule="{rule}"}} 0' in text
    assert "dt_lint_ok 1" in text
    witness_reset()


def test_replication_metrics_v3_derived_keys():
    """Satellite (a): the v2 scalar pair is derived from the v3
    histogram so old scrapers keep working."""
    from diamond_types_tpu.replicate.metrics import ReplicationMetrics
    rm = ReplicationMetrics()
    for s in (0.1, 0.3):
        rm.observe_handoff_latency(s)
    snap = rm.snapshot()
    assert snap["version"] == 8
    assert snap["latencies"]["handoff"]["count"] == 2
    assert snap["handoffs"]["latency_s_total"] == pytest.approx(0.4)
    assert snap["handoffs"]["latency_s_max"] == pytest.approx(0.3)
    assert snap["latencies"]["handoff"]["p99"] > 0


# ---- end-to-end: server + proxied trace ----------------------------------

def _serve_pair(sample_rate=1.0):
    from diamond_types_tpu.replicate import attach_replication
    from diamond_types_tpu.tools.server import serve
    httpds, addrs = [], []
    for _ in range(2):
        httpd = serve(port=0, engine="host", serve_shards=2,
                      obs_opts={"sample_rate": sample_rate})
        httpds.append(httpd)
        addrs.append(f"127.0.0.1:{httpd.server_address[1]}")
    nodes = []
    for i, httpd in enumerate(httpds):
        nodes.append(attach_replication(
            httpd, addrs[i], [a for a in addrs if a != addrs[i]],
            lease_ttl_s=5.0, backoff_base_s=0.01, backoff_cap_s=0.05))
        threading.Thread(target=httpd.serve_forever,
                         daemon=True).start()
    return httpds, nodes, addrs


def _teardown(httpds):
    for h in httpds:
        h.shutdown()
        h.server_close()


def _post(addr, path, obj):
    req = urllib.request.Request(f"http://{addr}{path}",
                                 data=json.dumps(obj).encode("utf8"))
    with urllib.request.urlopen(req, timeout=5) as r:
        return r.status, json.loads(r.read())


def test_proxied_edit_yields_one_stitched_trace():
    """Acceptance: a proxied edit across a two-server mesh produces ONE
    trace — proxy hop, remote http span, ownership gate, admit, flush,
    device sync — with parentage intact across the HTTP boundary."""
    httpds, nodes, addrs = _serve_pair(sample_rate=1.0)
    try:
        # a doc owned by server 1, posted to server 0 -> proxied
        doc = next(d for d in (f"tdoc-{i}" for i in range(64))
                   if nodes[0].desired_owner(d) == addrs[1])
        status, out = _post(addrs[0], f"/doc/{doc}/edit",
                            {"agent": "tracer", "version": [],
                             "ops": [{"kind": "ins", "pos": 0,
                                      "text": "hello"}]})
        assert status == 200 and out.get("version")
        httpds[1].store.scheduler.drain()

        # HTTP spans end in the handlers' `finally`, after the
        # response bytes are on the wire — poll until both hops land
        want = {"http.doc_edit", "repl.proxy", "serve.admit",
                "serve.ownership_gate", "serve.flush",
                "serve.device_sync"}
        deadline = time.monotonic() + 3.0
        while True:
            spans = (httpds[0].store.obs.tracer.spans()
                     + httpds[1].store.obs.tracer.spans())
            roots = [s for s in spans
                     if s["name"] == "http.doc_edit"
                     and s["parent"] is None]
            mine = ([s for s in spans
                     if s["trace"] == roots[0]["trace"]]
                    if roots else [])
            names = {s["name"] for s in mine}
            hops = sum(1 for s in mine if s["name"] == "http.doc_edit")
            if (want <= names and hops == 2) or \
                    time.monotonic() > deadline:
                break
            time.sleep(0.01)
        assert roots, [s["name"] for s in spans]
        trace_id = roots[0]["trace"]
        assert want <= names, names
        assert hops == 2
        by_id = {s["span"]: s for s in mine}
        by_name = {}
        for s in mine:
            by_name.setdefault(s["name"], []).append(s)
        # every non-root span's parent is in the same trace
        for s in mine:
            if s["parent"] is not None:
                assert s["parent"] in by_id, s
        # the exact chain: root http -> proxy -> remote http -> admit
        # -> {gate, and flush -> device_sync}
        proxy = by_name["repl.proxy"][0]
        assert proxy["parent"] == roots[0]["span"]
        remote_http = [s for s in by_name["http.doc_edit"]
                       if s["parent"] == proxy["span"]]
        assert remote_http
        admit = by_name["serve.admit"][0]
        assert admit["parent"] == remote_http[0]["span"]
        assert by_name["serve.ownership_gate"][0]["parent"] \
            == admit["span"]
        flush = by_name["serve.flush"][0]
        assert flush["parent"] == admit["span"]
        assert by_name["serve.device_sync"][0]["parent"] \
            == flush["span"]
        # the mutation itself landed (proxied, not just traced)
        with urllib.request.urlopen(f"http://{addrs[1]}/doc/{doc}",
                                    timeout=5) as r:
            assert r.read().decode("utf8") == "hello"
    finally:
        _teardown(httpds)


def test_metrics_endpoint_formats_and_debug_events():
    """Satellite (b) + acceptance: /metrics serves JSON by default and
    Prometheus text with `?format=prom`, both with Cache-Control:
    no-store; dt_flush_latency_seconds shows non-zero counts after
    traffic; /debug/events dumps the flight-recorder ring."""
    from diamond_types_tpu.tools.server import serve
    httpd = serve(port=0, engine="host", serve_shards=2,
                  obs_opts={"sample_rate": 1.0})
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        for i in range(3):
            _post(addr, f"/doc/m{i}/edit",
                  {"agent": "a", "version": [],
                   "ops": [{"kind": "ins", "pos": 0, "text": "x"}]})
        httpd.store.scheduler.drain()
        with urllib.request.urlopen(f"http://{addr}/metrics",
                                    timeout=5) as r:
            assert r.headers["Cache-Control"] == "no-store"
            assert r.headers["Content-Type"].startswith(
                "application/json")
            doc = json.loads(r.read())
        assert doc["serve"]["version"] == ServeMetrics.SCHEMA_VERSION
        assert doc["serve"]["latencies"]["flush"]["count"] >= 1
        assert doc["obs"]["trace"]["started"] >= 1
        assert any(row["count"] >= 1
                   for row in doc["obs"]["http"]["http_request"])
        with urllib.request.urlopen(
                f"http://{addr}/metrics?format=prom", timeout=5) as r:
            assert r.headers["Cache-Control"] == "no-store"
            assert r.headers["Content-Type"] == CONTENT_TYPE
            text = r.read().decode("utf8")
        _check_prom(text)
        m = re.search(r"^dt_flush_latency_seconds_count (\d+)$", text,
                      re.M)
        assert m and int(m.group(1)) >= 1, "flush histogram not exposed"
        with urllib.request.urlopen(f"http://{addr}/debug/events",
                                    timeout=5) as r:
            ev = json.loads(r.read())
        assert "events" in ev and "recorded" in ev
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_unsampled_requests_skip_span_buffer():
    """At sample_rate=0 the server's request path must produce zero
    buffered spans (histograms still record — they are always on)."""
    from diamond_types_tpu.tools.server import serve
    httpd = serve(port=0, engine="host", serve_shards=2,
                  obs_opts={"sample_rate": 0.0})
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        _post(addr, "/doc/z/edit",
              {"agent": "a", "version": [],
               "ops": [{"kind": "ins", "pos": 0, "text": "y"}]})
        obs = httpd.store.obs
        assert obs.tracer.spans() == []
        assert obs.tracer.stats()["sampled_out"] >= 1
        # the histogram records in the handler's `finally`, which runs
        # after the response hits the wire — give it a beat
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            rows = obs.hist.snapshot().get("http_request", [])
            if sum(r["count"] for r in rows) >= 1:
                break
            time.sleep(0.01)
        assert sum(r["count"] for r in rows) >= 1
    finally:
        httpd.shutdown()
        httpd.server_close()
