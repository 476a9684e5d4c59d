"""Convergence-tracing tests (obs/journey.py + obs/assemble.py + the
serving/replication wiring): stage-stamp semantics (first-wins begin,
the advert-after-apply guard, FIFO eviction), clock-skew-robust
cross-host assembly with an exact critical-path decomposition, the
disabled-journey zero-allocation pin, the visibility_p99 SLO driven
ok -> burning -> ok on seeded lags, the /debug/trace endpoints + the
dt-trace CLI, prom zero-fill for the dt_journey_* / dt_convergence_*
families, and the two-server acceptance run assembling one proxied
edit's trace across both hosts. Tier-1 safe: in-process servers on
ephemeral ports, no TPU.
"""

import json
import threading
import time
import tracemalloc
import types
import urllib.request

import pytest

from diamond_types_tpu.obs import Observability
from diamond_types_tpu.obs.assemble import (aggregate, assemble_trace,
                                            estimate_offset,
                                            render_human)
from diamond_types_tpu.obs.journey import (CONVERGENCE_PREFIX,
                                           PEER_STAGES, STAGES,
                                           VISIBILITY_SERIES,
                                           OpJourney)
from diamond_types_tpu.obs.prom import render_metrics
from diamond_types_tpu.obs.slo import Objective, SloEngine
from diamond_types_tpu.obs.timeseries import TimeSeries

pytestmark = pytest.mark.journey


class _Clock:
    """Injectable monotonic clock (mirrors test_telemetry.py)."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


# ---- stage stamping -------------------------------------------------------

def test_journey_stage_stamps_waterfall_and_convergence_lag():
    clk = _Clock(100.0)
    ts = TimeSeries(window_s=10.0, n_windows=8, clock=clk)
    j = OpJourney(ts=ts, clock=clk)
    key = j.begin("alice", 7, doc="d1", trace="t-abc")
    assert key == "t-abc"
    # first begin wins: a re-announce (the scheduler's begin with no
    # identity) must not reset t_admitted or double-count `admitted`
    clk.t = 100.5
    assert j.begin(None, None, doc="d1", trace="t-abc") == "t-abc"
    assert j.snapshot()["stages"]["admitted"] == 1
    for stage in ("queued", "planned", "adopted", "wal_durable"):
        clk.t += 0.1
        j.stamp(key, stage)
    # first stamp wins per stage
    j.stamp(key, "queued", t=999.0)
    entry = j.journey(key)
    assert entry["agent"] == "alice" and entry["seq"] == 7
    assert entry["stages"]["admitted"] == 100.0
    assert entry["stages"]["queued"] == pytest.approx(100.6)
    # peer-side facts arrive via the doc index (AE knows doc, not trace)
    clk.t = 101.2
    j.stamp_doc("d1", "ae_shipped", peer="p1")
    # the advert guard: an advert BEFORE the peer applied proves
    # nothing about this edit's visibility — the stamp is skipped
    j.stamp_doc("d1", "advert_usable", peer="p1", t=101.25)
    assert "advert_usable" not in j.journey(key)["peers"]["p1"]
    j.stamp_doc("d1", "applied_at_peer", peer="p1", t=101.3)
    j.stamp_doc("d1", "advert_usable", peer="p1", t=101.5)
    peers = j.journey(key)["peers"]["p1"]
    assert set(peers) == set(PEER_STAGES)
    # convergence lag = advert_usable - admitted, double-written into
    # the per-peer family and the SLO aggregate
    lag = j.lag_summary()["p1"]
    assert lag["n"] == 1
    assert lag["mean_s"] == pytest.approx(1.5)
    assert ts.count_over(VISIBILITY_SERIES, 0.0, 300.0)[1] == 1
    assert ts.count_over(f"{CONVERGENCE_PREFIX}.p1", 0.0, 300.0)[1] == 1
    # the waterfall orders rows by offset from admitted
    rows = j.waterfall(key)
    assert rows[0] == ("admitted", 0.0, None)
    offs = [r[1] for r in rows]
    assert offs == sorted(offs)
    assert ("advert_usable", 1.5, "p1") in rows
    snap = j.snapshot()
    assert snap["stages"]["advert_usable"] == 1
    assert snap["stages"]["device_replayed"] == 0
    json.dumps(snap)


def test_journey_fifo_eviction_and_doc_index_cleanup():
    j = OpJourney(capacity=4, clock=_Clock())
    for i in range(6):
        j.begin(f"a{i}", i, doc=f"d{i}")
    assert j.snapshot()["tracked"] == 4
    assert j.snapshot()["dropped"] == 2
    # evicted journeys leave no doc-index residue: stamping their doc
    # is a no-op, stamping a live doc still lands
    j.stamp_doc("d0", "wal_durable")
    j.stamp_doc("d5", "wal_durable")
    assert j.journey("a5:5")["stages"].get("wal_durable") is not None
    assert j.snapshot()["stages"]["wal_durable"] == 1


def test_disabled_journey_single_branch_zero_alloc():
    """The disabled journey is ONE branch per call: tracemalloc must
    attribute zero allocations to journey.py across 200 stamp cycles
    (same contract as the disabled tracer/TimeSeries)."""
    import diamond_types_tpu.obs.journey as j_mod
    j = OpJourney(enabled=False)
    j.begin("a", 1, "d")
    j.stamp("a:1", "queued")
    j.stamp_doc("d", "wal_durable")
    files = {j_mod.__file__}

    def _cycle():
        for _ in range(200):
            j.begin("a", 1, "d")
            j.stamp("a:1", "queued")
            j.stamp_doc("d", "wal_durable", "p")

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
    assert j.stamped == 0 and j.snapshot()["tracked"] == 0


# ---- skew-robust assembly -------------------------------------------------

def test_skewed_two_host_assembly_monotonic_and_exact_critical_path(
        monkeypatch):
    """Two hosts on clocks 5s apart (faults.py skew bookkeeping) plus
    a deliberately asymmetric RTT on one fetch: after alignment the
    monotonic repair must keep every child at or after its parent, and
    the critical path's owned segments must telescope to exactly the
    root's wall time."""
    import diamond_types_tpu.replicate.faults as faults_mod
    truth = _Clock(0.0)
    monkeypatch.setattr(faults_mod, "time",
                        types.SimpleNamespace(monotonic=truth))
    fi = faults_mod.FaultInjector()
    fi.set_clock_skew("a", 3.0)
    fi.set_clock_skew("b", -2.0)

    def at(host, true_t):
        truth.t = true_t
        return fi.now(host)

    tid = "t-skew"
    spans_a = [
        {"trace": tid, "span": "s-root", "parent": None,
         "name": "http.doc_edit", "t0": at("a", 10.0), "dur_s": 0.100},
        {"trace": tid, "span": "s-proxy", "parent": "s-root",
         "name": "repl.proxy", "t0": at("a", 10.010), "dur_s": 0.080},
    ]
    spans_b = [
        {"trace": tid, "span": "s-rhttp", "parent": "s-proxy",
         "name": "http.doc_edit", "t0": at("b", 10.020), "dur_s": 0.060},
        {"trace": tid, "span": "s-admit", "parent": "s-rhttp",
         "name": "serve.admit", "t0": at("b", 10.025), "dur_s": 0.010},
    ]
    # host a fetched with a symmetric zero-RTT probe: exact offset
    fetch_a = {"host": "a", "spans": spans_a,
               "t_send": 20.0, "t_recv": 20.0, "now": at("a", 20.0)}
    # host b's probe is asymmetric: the server sampled `now` at
    # t_recv, not the midpoint, so the estimate is off by RTT/2 =
    # 25ms — enough to order the remote hop before its proxy parent
    fetch_b = {"host": "b", "spans": spans_b,
               "t_send": 20.0, "t_recv": 20.05, "now": at("b", 20.05)}
    assert estimate_offset(0.0, 2.0, 11.0) == pytest.approx(10.0)
    rep = assemble_trace(tid, [fetch_a, fetch_b])
    assert rep["hosts"] == ["a", "b"]
    assert rep["spans"] == 4 and rep["orphans"] == 0
    assert rep["root"] == {"name": "http.doc_edit", "host": "a"}
    # monotonic repair: no waterfall row precedes the root, and every
    # child starts at or after its parent
    by_span = {r["span"]: r for r in rep["waterfall"]}
    for r in rep["waterfall"]:
        assert r["t0_rel_s"] >= 0.0
        if r["parent"] is not None:
            assert r["t0_rel_s"] >= by_span[r["parent"]]["t0_rel_s"]
    # residual skew DID violate causality pre-repair: the remote hop
    # got clamped up to its proxy parent's start
    assert by_span["s-rhttp"]["t0_rel_s"] == \
        by_span["s-proxy"]["t0_rel_s"]
    # exact telescoping decomposition along the 4-deep chain
    cp = rep["critical_path"]
    assert [s["name"] for s in cp] == \
        ["http.doc_edit", "repl.proxy", "http.doc_edit", "serve.admit"]
    assert [s["host"] for s in cp] == ["a", "a", "b", "b"]
    assert [s["owned_s"] for s in cp] == \
        pytest.approx([0.020, 0.020, 0.050, 0.010])
    assert rep["critical_path_s"] == pytest.approx(rep["wall_s"],
                                                   abs=1e-6)
    t0s = [s["t0_rel_s"] for s in cp]
    assert t0s == sorted(t0s)
    # aggregation attributes ownership across (name, host)
    agg = aggregate([rep, rep])
    assert agg["traces"] == 2
    assert agg["total_owned_s"] == pytest.approx(2 * rep["wall_s"])
    assert agg["owners"][0]["name"] == "http.doc_edit"
    assert sum(r["share"] for r in agg["owners"]) == pytest.approx(1.0)
    text = render_human(rep, agg)
    assert "== critical path" in text and "@b owns" in text


def test_assemble_missing_host_degrades_to_orphans():
    tid = "t-x"
    fetches = [{"host": "a", "offset_s": 0.0, "spans": [
        {"trace": tid, "span": "r", "parent": None, "name": "root",
         "t0": 1.0, "dur_s": 0.5},
        {"trace": tid, "span": "k", "parent": "missing",
         "name": "stray", "t0": 1.2, "dur_s": 0.1},
    ]}]
    rep = assemble_trace(tid, fetches)
    # the span whose parent lives on an unreachable host becomes a
    # secondary root, reported as an orphan — never dropped silently
    assert rep["orphans"] == 1 and rep["spans"] == 2
    assert rep["critical_path_s"] == pytest.approx(rep["wall_s"])
    empty = assemble_trace("nope", fetches)
    assert empty["root"] is None and empty["spans"] == 0
    assert "no spans found" in render_human(empty)


def test_assemble_survives_span_id_collision_cycle():
    """Span-id collisions across hosts (or a malicious peer) can form
    parent CYCLES in the merged set — the tree walk must truncate the
    cycle, not hang the CLI."""
    tid = "t-cyc"
    fetches = [
        {"host": "a", "offset_s": 0.0, "spans": [
            {"trace": tid, "span": "r", "parent": None, "name": "root",
             "t0": 1.0, "dur_s": 0.5},
            {"trace": tid, "span": "x", "parent": "r", "name": "kid",
             "t0": 1.1, "dur_s": 0.3},
            {"trace": tid, "span": "y", "parent": "x", "name": "gk",
             "t0": 1.2, "dur_s": 0.2},
        ]},
        # the colliding host reuses id "x", parented on "y": x -> y ->
        # x is a cycle once both hosts' records are merged
        {"host": "b", "offset_s": 0.0, "spans": [
            {"trace": tid, "span": "x", "parent": "y", "name": "dup",
             "t0": 1.25, "dur_s": 0.1},
        ]},
    ]
    rep = assemble_trace(tid, fetches)
    assert rep["root"]["name"] == "root" and rep["spans"] == 4
    assert rep["critical_path"][0]["name"] == "root"
    assert len(rep["critical_path"]) <= 4


# ---- visibility SLO -------------------------------------------------------

def test_visibility_slo_ok_burning_ok_with_lag_verdict_column():
    """Seeded replication delay drives visibility_p99 ok -> burning ->
    ok, and the soak-verdict convergence-lag column reflects the seeded
    lags (the column replicate/soak.py + rebalance_soak.py embed)."""
    clk = _Clock()
    ts = TimeSeries(window_s=10.0, n_windows=60, clock=clk)
    j = OpJourney(capacity=1024, ts=ts, clock=clk)
    eng = SloEngine(ts, objectives=[
        Objective("visibility_p99", VISIBILITY_SERIES, threshold_s=0.1,
                  target=0.99, fast_window_s=60.0,
                  slow_window_s=300.0)])

    def converge(n, lag_s, tag):
        for i in range(n):
            key = j.begin(f"{tag}{i}", i, doc=f"{tag}d{i}", t=0.0)
            j.stamp(key, "applied_at_peer", peer="peer-1", t=0.0)
            j.stamp(key, "advert_usable", peer="peer-1", t=lag_s)

    def state():
        return eng.evaluate()[0]["state"]

    converge(100, 0.005, "g")          # healthy replication
    assert state() == "ok"
    converge(60, 2.0, "b")             # seeded replication delay
    assert state() == "burning"
    v = eng.verdict()
    assert v["slo_ok"] is False and v["burning"] == ["visibility_p99"]
    # the verdict's convergence-lag column carries the seeded delay
    col = j.lag_summary()
    assert col["peer-1"]["n"] == 160
    assert col["peer-1"]["max_s"] == pytest.approx(2.0)
    assert col["peer-1"]["mean_s"] > 0.5
    clk.t = 400.0                      # the bad windows age out
    converge(100, 0.005, "h")
    assert state() == "ok"
    assert eng.verdict()["slo_ok"] is True
    assert eng.snapshot()["objectives"][0]["transitions"] >= 2


# ---- prom zero-fill -------------------------------------------------------

def test_prom_journey_and_convergence_zero_fill():
    """A fresh server with zero traffic still exposes every
    dt_journey_stage_total stage row and the peer="all" convergence
    rollup, so dashboards never see series flicker into existence."""
    obs = Observability(sample_rate=0.0)
    text = render_metrics({"obs": obs.snapshot()})
    for stage in STAGES:
        assert f'dt_journey_stage_total{{stage="{stage}"}} 0' in text, \
            stage
    assert "dt_journey_enabled 1" in text
    assert "dt_journey_tracked 0" in text
    assert "dt_journey_stamps_total 0" in text
    assert "dt_journey_dropped_total 0" in text
    assert 'dt_convergence_lag_count{peer="all"} 0' in text
    assert 'dt_convergence_lag_seconds_sum{peer="all"} 0' in text
    assert 'dt_convergence_lag_seconds_max{peer="all"} 0' in text
    # journey=False drops the tier to a disabled stub, still scraped
    off = Observability(sample_rate=0.0, journey=False)
    assert not off.journey.enabled
    assert "dt_journey_enabled 0" in \
        render_metrics({"obs": off.snapshot()})


# ---- server endpoints + CLI ----------------------------------------------

def _serve_one(**obs_opts):
    from diamond_types_tpu.tools.server import serve
    opts = {"sample_rate": 0.0}
    opts.update(obs_opts)
    httpd = serve(port=0, engine="host", serve_shards=2, obs_opts=opts)
    addr = f"127.0.0.1:{httpd.server_address[1]}"
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, addr


def _get_json(addr, path):
    with urllib.request.urlopen(f"http://{addr}{path}", timeout=5) as r:
        return json.loads(r.read())


def _post(addr, path, obj):
    req = urllib.request.Request(f"http://{addr}{path}",
                                 data=json.dumps(obj).encode("utf8"))
    with urllib.request.urlopen(req, timeout=5) as r:
        return r.status, json.loads(r.read())


def _edit(addr, doc, text="hello"):
    return _post(addr, f"/doc/{doc}/edit",
                 {"agent": "journey", "version": [],
                  "ops": [{"kind": "ins", "pos": 0, "text": text}]})


def _wait_trace(obs_list, root_name="http.doc_edit", deadline_s=3.0):
    """HTTP spans end in the handlers' `finally` after the response is
    on the wire — poll until the root span lands in a ring."""
    deadline = time.monotonic() + deadline_s
    while time.monotonic() < deadline:
        for obs in obs_list:
            for s in obs.tracer.spans():
                if s["name"] == root_name and s["parent"] is None:
                    return s["trace"]
        time.sleep(0.01)
    raise AssertionError("no sampled root span landed")


def test_debug_trace_endpoints_and_dt_trace_cli(capsys):
    httpd, addr = _serve_one(sample_rate=1.0)
    try:
        status, _out = _edit(addr, "jdoc")
        assert status == 200
        httpd.store.scheduler.drain()
        obs = httpd.store.obs
        tid = _wait_trace([obs])
        # journey stamps landed along the single-host pipeline
        stages = obs.journey.snapshot()["stages"]
        for stage in ("admitted", "queued", "planned", "adopted"):
            assert stages[stage] >= 1, (stage, stages)
        # /debug/traces: the index lists the trace, newest first
        idx = _get_json(addr, "/debug/traces")
        assert idx["host"] == "local" and idx["now"] > 0
        row = next(r for r in idx["traces"] if r["trace"] == tid)
        assert row["root"] == "http.doc_edit" and row["spans"] >= 3
        # /debug/trace/<id>: this host's spans + its monotonic now
        one = _get_json(addr, f"/debug/trace/{tid}")
        assert one["trace"] == tid and one["host"] == "local"
        assert all(s["trace"] == tid for s in one["spans"])
        assert {s["name"] for s in one["spans"]} >= \
            {"http.doc_edit", "serve.admit"}
        # an unknown id is an empty fetch, not an error
        assert _get_json(addr, "/debug/trace/zzzz")["spans"] == []
        from diamond_types_tpu.tools import cli
        # listing mode
        assert cli.main(["dt-trace", addr]) == 0
        out = capsys.readouterr().out
        assert tid in out and "recent traces" in out
        # assembly mode: single host, JSON
        assert cli.main(["dt-trace", addr, tid, "--json"]) == 0
        rep = json.loads(capsys.readouterr().out)["traces"][0]
        assert rep["trace"] == tid and rep["root"] is not None
        assert rep["critical_path_s"] == pytest.approx(rep["wall_s"],
                                                       abs=1e-5)
        # a bogus id exits nonzero (no root assembled)
        assert cli.main(["dt-trace", addr, "zzzz"]) == 1
        capsys.readouterr()
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_obs_watch_convergence_and_devprof_panels(capsys):
    from diamond_types_tpu.obs.devprof import PROFILER, note_jit_lookup
    httpd, addr = _serve_one(sample_rate=1.0)
    try:
        obs = httpd.store.obs
        key = obs.journey.begin("w", 1, doc="wdoc")
        obs.journey.stamp(key, "applied_at_peer", peer="peer-9")
        obs.journey.stamp(key, "advert_usable", peer="peer-9")
        # the replay jit families surface in the device panel
        PROFILER.enabled = True
        note_jit_lookup("fused", True)
        note_jit_lookup("mesh", False)
        from diamond_types_tpu.tools import cli
        rc = cli.main(["obs-watch", addr, "--rounds", "1",
                       "--interval", "0"])
        out = capsys.readouterr().out
        assert rc == 0
        assert "== convergence (tracked=1" in out
        assert "lag peer-9" in out
        assert "advert_usable=1" in out
        assert "== device (jit cache) ==" in out
        panel = out.split("== device (jit cache) ==")[1]
        assert "fused " in panel and "mesh " in panel
        assert "visibility_p99" in out
    finally:
        PROFILER.enabled = False
        httpd.shutdown()
        httpd.server_close()


# ---- two-server acceptance ------------------------------------------------

def _serve_pair():
    from diamond_types_tpu.replicate import attach_replication
    from diamond_types_tpu.tools.server import serve
    httpds, addrs = [], []
    for _ in range(2):
        # follower_reads attaches read/follower.py's FollowerIndex —
        # the advert_usable stamp rides its note_advert
        httpd = serve(port=0, engine="host", serve_shards=2, follower_reads=True,
                      obs_opts={"sample_rate": 1.0})
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


def test_cross_host_trace_assembly_and_full_journey_acceptance(capsys):
    """Acceptance: one edit proxied across a two-server mesh yields a
    journey stamped at every owner-path stage, a cross-host trace
    whose assembly spans both hosts, and a critical path that sums to
    the trace's wall time."""
    httpds, nodes, addrs = _serve_pair()
    try:
        # a doc owned by server 1, posted to server 0 -> proxied
        doc = next(d for d in (f"jdoc-{i}" for i in range(64))
                   if nodes[0].desired_owner(d) == addrs[1])
        status, out = _edit(addrs[0], doc)
        assert status == 200 and out.get("version")
        httpds[1].store.scheduler.drain()
        tid = _wait_trace([h.store.obs for h in httpds])
        journey = httpds[1].store.obs.journey
        # AE round 1 pushes the patch (ae_shipped + applied_at_peer);
        # a later round's piggybacked frontier advert, now dominating,
        # lands advert_usable — poll rounds until the journey closes
        deadline = time.monotonic() + 10.0
        while time.monotonic() < deadline:
            nodes[1].antientropy.run_round()
            entry = journey.journey(tid)
            if entry and "advert_usable" in \
                    (entry["peers"].get(addrs[0]) or {}):
                break
            time.sleep(0.05)
        entry = journey.journey(tid)
        assert entry is not None, journey.snapshot()
        assert entry["doc"] == doc and entry["agent"] == "journey"
        # every owner-path stage (no data_dir -> no wal_durable; host
        # engine -> no device_replayed) plus all three peer stages
        for stage in ("admitted", "queued", "planned", "adopted"):
            assert stage in entry["stages"], (stage, entry)
        peer_slots = entry["peers"][addrs[0]]
        assert set(peer_slots) == set(PEER_STAGES)
        # stamps are causally ordered along the waterfall
        rows = journey.waterfall(tid)
        assert rows[0][0] == "admitted"
        assert [r[1] for r in rows] == sorted(r[1] for r in rows)
        # the convergence-lag column names the follower
        col = journey.lag_summary()
        assert col[addrs[0]]["n"] >= 1
        assert col[addrs[0]]["max_s"] > 0.0
        # and the live series feeds the visibility_p99 objective
        slo = {o["name"]: o
               for o in httpds[1].store.obs.slo.evaluate()}
        assert slo["visibility_p99"]["fast"]["total"] >= 1
        # cross-host assembly via the CLI: both hosts, exact critical
        # path, ownership spanning the proxy hop
        from diamond_types_tpu.tools import cli
        rc = cli.main(["dt-trace", addrs[0], tid,
                       "--peers", addrs[1], "--json"])
        assert rc == 0
        rep = json.loads(capsys.readouterr().out)["traces"][0]
        assert sorted(rep["hosts"]) == sorted(addrs)
        assert rep["root"]["name"] == "http.doc_edit"
        assert rep["root"]["host"] == addrs[0]
        names = {r["name"] for r in rep["waterfall"]}
        assert {"http.doc_edit", "repl.proxy", "serve.admit"} <= names
        hosts_on_path = {s["host"] for s in rep["critical_path"]}
        assert addrs[0] in hosts_on_path
        assert rep["critical_path_s"] == pytest.approx(rep["wall_s"],
                                                       abs=1e-5)
        assert rep["wall_s"] > 0.0
        # human rendering round-trips the same assembly
        rc = cli.main(["dt-trace", addrs[0], tid, "--peers", addrs[1]])
        out = capsys.readouterr().out
        assert rc == 0
        assert f"== trace {tid}" in out and "2 hosts" in out
        assert "== critical path" in out
    finally:
        for h in httpds:
            h.shutdown()
            h.server_close()
