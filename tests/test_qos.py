"""Adaptive-admission tests (qos/): the closed-loop deadline
controller, mesh-aware shedding, per-tenant token-bucket isolation,
the per-class admission queue wiring, and the dt_qos_* export surface
(prom families, /metrics + /debug/qos, scorecard block).

The controller tests run on a fake clock against a fake Observability
(a TimeSeries the test drives directly), so convergence and
hysteresis are deterministic. The e2e test boots a real server with
--qos semantics and uses the force_mesh_state hook to verify the
shed-before-interactive ordering over live HTTP.
"""

import json
import threading
import urllib.error
import urllib.request

import pytest

from diamond_types_tpu.obs.prom import render_metrics
from diamond_types_tpu.obs.scorecard import build_scorecard, diff_scorecards
from diamond_types_tpu.obs.timeseries import TimeSeries
from diamond_types_tpu.qos import (QOS_CLASS_KEYS, QOS_CLASSES,
                                   QosController, ShedPolicy, TokenBucket,
                                   classify_headers, default_classes,
                                   merge_snapshots, tenant_of)
from diamond_types_tpu.qos.metrics import QosMetrics
from diamond_types_tpu.serve.admission import AdmissionQueue, Backpressure

pytestmark = pytest.mark.qos


class FakeClock:
    def __init__(self, t: float = 100.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> float:
        self.t += dt
        return self.t


class FakeObs:
    """Just enough Observability surface for QosController.step."""

    def __init__(self, ts) -> None:
        self.ts = ts


def make_controller(clock, flush_deadline_s=0.05, n_shards=1,
                    flush_docs=8, **kw):
    q = AdmissionQueue(n_shards, max_pending=64, flush_docs=flush_docs,
                      flush_deadline_s=flush_deadline_s)
    ctl = QosController(clock=clock, **kw)
    ctl.bind(q)
    ctl.attach_obs(FakeObs(TimeSeries(window_s=1.0, n_windows=600,
                                      clock=clock)))
    return ctl, q


# ---- taxonomy ------------------------------------------------------------

def test_classify_headers():
    assert classify_headers({"X-DT-QoS": "bulk"}) == "bulk"
    assert classify_headers({"X-DT-QoS": " Catchup "}) == "catchup"
    # unknown explicit value must not deprioritize a user edit
    assert classify_headers({"X-DT-QoS": "speedy"}) == "interactive"
    assert classify_headers({"X-DT-Replication": "1"}) == "catchup"
    assert classify_headers({"X-DT-QoS": "bulk",
                             "X-DT-Replication": "1"}) == "bulk"
    assert classify_headers({}) == "interactive"


def test_tenant_of_grammar():
    assert tenant_of("t0-doc001") == "t0"
    assert tenant_of("t17-bulk000") == "t17"
    assert tenant_of("bank0000007") is None
    assert tenant_of("tx-doc") is None
    assert tenant_of(None) is None


def test_default_classes_contract():
    classes = default_classes(0.05)
    inter = classes["interactive"]
    # interactive ceiling IS the static deadline: adaptive batching may
    # only ever tighten the latency-sensitive class
    assert inter.ceiling_s == 0.05 and not inter.sheddable
    assert classes["bulk"].sheddable and classes["catchup"].sheddable
    assert classes["bulk"].ceiling_s == pytest.approx(2.0)
    # clamp = floors/ceilings enforcement
    assert inter.clamp(10.0) == inter.ceiling_s
    assert inter.clamp(0.0) == inter.floor_s
    b = classes["bulk"]
    assert b.floor_s <= b.clamp(0.4) <= b.ceiling_s


# ---- the control loop (fake clock) ---------------------------------------

def test_controller_stretches_bulk_under_moderate_load():
    clock = FakeClock()
    ctl, q = make_controller(clock)
    base = ctl.classes["bulk"].deadline_s
    ts = ctl.metrics.ts
    for _ in range(40):
        ts.inc("qos.admitted.bulk", 5.0)   # ~20/s on the fake clock
        clock.advance(0.25)
        ctl.step()
    # gap=8 docs at 20/s => ~0.4s fill time > the 0.25s base deadline
    got = ctl.effective_deadline(0, "bulk")
    assert got > base * 1.2
    assert got <= ctl.classes["bulk"].ceiling_s
    assert ctl.metrics.snapshot()["controller"]["stretched"] >= 1


def test_controller_shrinks_to_floor_when_idle():
    clock = FakeClock()
    ctl, q = make_controller(clock)
    ts = ctl.metrics.ts
    for _ in range(20):
        ts.inc("qos.admitted.bulk", 5.0)
        clock.advance(0.25)
        ctl.step()
    stretched = ctl.effective_deadline(0, "bulk")
    # arrivals stop; once the rate window drains, fill time is
    # unreachable and the deadline drops to the floor — lone docs
    # flush early instead of paying occupancy nobody will deliver
    for _ in range(60):
        clock.advance(0.25)
        ctl.step()
    floor = ctl.classes["bulk"].floor_s
    got = ctl.effective_deadline(0, "bulk")
    assert got < stretched
    assert got == pytest.approx(floor, rel=0.25)


def test_controller_hysteresis_holds_on_noise():
    clock = FakeClock()
    ctl, q = make_controller(clock, deadband=0.1)
    ts = ctl.metrics.ts
    for _ in range(40):
        ts.inc("qos.admitted.bulk", 5.0)
        clock.advance(0.25)
        ctl.step()
    before = ctl.metrics.snapshot()["controller"]
    # +/-5% oscillation around the converged rate sits inside the 10%
    # deadband: the published table must hold, not thrash
    for i in range(40):
        ts.inc("qos.admitted.bulk", 5.25 if i % 2 else 4.75)
        clock.advance(0.25)
        ctl.step()
    after = ctl.metrics.snapshot()["controller"]
    held = after["held"] - before["held"]
    moved = (after["stretched"] - before["stretched"]) \
        + (after["shrunk"] - before["shrunk"])
    assert held > moved * 3


def test_slo_guard_pins_class_to_floor():
    clock = FakeClock()
    ctl, q = make_controller(clock)

    class BurnSlo:
        def evaluate(self):
            return [{"name": "queue_wait_p99", "state": "burning",
                     "fast": {"burn": 20.0}}]

    ctl.obs.slo = BurnSlo()
    ts = ctl.metrics.ts
    for _ in range(40):
        ts.inc("qos.admitted.bulk", 5.0)   # load that would stretch
        clock.advance(0.25)
        ctl.step()
    # bulk's objective burns => latency wins over occupancy
    assert ctl.effective_deadline(0, "bulk") == pytest.approx(
        ctl.classes["bulk"].floor_s, rel=0.25)
    assert ctl.metrics.snapshot()["controller"]["floors"] > 0


def test_interactive_never_exceeds_static_deadline():
    clock = FakeClock()
    ctl, q = make_controller(clock, flush_deadline_s=0.05)
    ts = ctl.metrics.ts
    for _ in range(60):
        # slow interactive trickle: naive fill-time would say "wait
        # seconds"; the ceiling must cap it at the static deadline
        ts.inc("qos.admitted.interactive", 0.5)
        clock.advance(0.25)
        ctl.step()
    assert ctl.effective_deadline(0, "interactive") <= 0.05 + 1e-9


def test_mesh_warning_pins_sheddable_to_ceiling():
    clock = FakeClock()
    ctl, q = make_controller(clock)
    ctl.force_mesh_state("warning", retry_after=0.0)
    for _ in range(40):
        clock.advance(0.25)
        ctl.step()
    assert ctl.effective_deadline(0, "bulk") == pytest.approx(
        ctl.classes["bulk"].ceiling_s, rel=0.2)
    # interactive is not sheddable: the warning leaves it alone
    assert ctl.effective_deadline(0, "interactive") <= 0.05 + 1e-9
    assert ctl.metrics.snapshot()["controller"]["ceilings"] > 0


# ---- shed policy ---------------------------------------------------------

def _burning_rows(burn=14.4):
    return [{"name": "visibility_p99", "state": "burning",
             "fast": {"burn": burn, "bad": 10, "total": 20}}]


def test_shed_orders_sheddable_before_interactive():
    clock = FakeClock()
    pol = ShedPolicy(metrics=QosMetrics(), clock=clock)
    pol.refresh(_burning_rows())
    ok_b, retry_b, why_b = pol.admit("bulk")
    ok_c, retry_c, why_c = pol.admit("catchup")
    ok_i, retry_i, why_i = pol.admit("interactive")
    assert not ok_b and not ok_c
    assert why_b.startswith("mesh_burn") and "visibility_p99" in why_b
    assert retry_b > 0 and retry_c > 0
    # the invariant the gate is named for: interactive survives while
    # the sheddable classes take the 429s
    assert ok_i and retry_i == 0.0
    snap = pol.metrics.snapshot()["classes"]
    assert snap["bulk"]["shed"] == 1 and snap["catchup"]["shed"] == 1
    assert snap["interactive"]["shed"] == 0


def test_shed_retry_after_scales_with_burn_and_clamps():
    pol = ShedPolicy()
    pol.refresh(_burning_rows(burn=2.0))
    assert pol.admit("bulk")[1] == pytest.approx(0.5)
    pol.refresh(_burning_rows(burn=1000.0))
    assert pol.admit("bulk")[1] == 10.0      # ceiling
    pol.refresh(_burning_rows(burn=0.1))
    assert pol.admit("bulk")[1] == 0.25      # floor


def test_warning_defers_instead_of_shedding():
    pol = ShedPolicy(metrics=QosMetrics())
    pol.refresh([{"name": "visibility_p99", "state": "warning",
                  "fast": {"burn": 2.0}}])
    ok, retry, why = pol.admit("bulk")
    assert ok and why == "deferred"
    assert pol.metrics.snapshot()["classes"]["bulk"]["deferred"] == 1


def test_convergence_lag_trips_mesh_gate():
    pol = ShedPolicy(lag_threshold_s=10.0)
    pol.refresh([], lag={"peer-b": {"mean_s": 30.0, "max_s": 60.0,
                                    "n": 4}})
    ok, retry, why = pol.admit("catchup")
    assert not ok and "convergence_lag:peer-b" in why


def test_token_bucket_refill():
    tb = TokenBucket(rate=10.0, burst=2.0, now=0.0)
    assert tb.take(0.0) and tb.take(0.0) and not tb.take(0.0)
    assert tb.take(0.1)                      # 1 token refilled
    assert not tb.take(0.1)


def test_hot_tenant_isolated_without_collateral():
    clock = FakeClock()
    pol = ShedPolicy(metrics=QosMetrics(), tenant_rate=100.0,
                     tenant_burst=10.0, isolation_factor=0.1,
                     clock=clock)
    pol.refresh([], hot_tenants={"t0"})
    # hot tenant gets burst*0.1 = 1 token; neighbor keeps its full 10
    assert pol.admit("interactive", tenant="t0")[0]
    ok, retry, why = pol.admit("interactive", tenant="t0")
    assert not ok and why == "tenant" and retry > 0
    for _ in range(10):
        assert pol.admit("interactive", tenant="t1")[0]


def test_hot_set_from_attrib_top_share():
    class Attrib:
        def top(self, dim, kind, n):
            return [("t9-doc000", 80.0, 0), ("t1-doc000", 10.0, 0),
                    ("bank0001", 10.0, 0)]

    pol = ShedPolicy(hot_share=0.5)
    assert pol.hot_tenants_from_attrib(Attrib()) == frozenset({"t9"})


# ---- admission queue wiring ----------------------------------------------

def test_queue_static_path_identical_when_detached():
    # no controller: every class sees the static trigger, the qos
    # field rides along inert
    q = AdmissionQueue(1, max_pending=8, flush_docs=4,
                       flush_deadline_s=0.05)
    q.submit(0, "a", 1, now=0.0, qos="bulk")
    q.submit(0, "b", 1, now=0.0)
    assert q.due(0.04) == []
    assert q.due(0.051) == [(0, 1, "deadline")]
    items = q.take(0, 1)
    assert [i.qos for i in items] == ["bulk", "interactive"]
    assert q.class_depth(0, "bulk") == 0


class StubCtl:
    """Published-table stand-in: per-class deadlines, full budgets."""

    def __init__(self, table):
        self.table = table

    def effective_deadline(self, shard, cls):
        return self.table[cls]

    def depth_budget(self, cls, max_pending):
        return max_pending


def test_queue_deadline_trigger_consults_controller_per_class():
    q = AdmissionQueue(1, max_pending=8, flush_docs=4,
                       flush_deadline_s=0.05)
    q.qos = StubCtl({"interactive": 0.01, "bulk": 0.5})
    q.submit(0, "bulky", 3, now=0.0, qos="bulk")       # bucket 4
    q.submit(0, "quick", 1, now=0.0, qos="interactive")  # bucket 1
    # interactive fires at its tightened deadline, bulk keeps waiting
    assert q.due(0.02) == [(0, 1, "deadline")]
    assert (0, 4, "deadline") in q.due(0.6)


def test_queue_mixed_bucket_interactive_not_starved_by_bulk():
    # regression: an interactive doc enqueued BEHIND a bulk doc in the
    # SAME shape bucket must flush on the interactive deadline, not
    # wait out the bulk item's stretched one — due() consults every
    # class's oldest entry, not just the first-inserted item's class
    q = AdmissionQueue(1, max_pending=8, flush_docs=4,
                       flush_deadline_s=0.05)
    q.qos = StubCtl({"interactive": 0.05, "bulk": 2.0})
    q.submit(0, "bulky", 1, now=0.0, qos="bulk")
    q.submit(0, "quick", 1, now=0.1, qos="interactive")
    assert q.due(0.1) == []
    # fires at the interactive item's own deadline (0.1 + 0.05), far
    # before bulk's stretched 2.0s window elapses
    assert q.due(0.16) == [(0, 1, "deadline")]


def test_queue_coalesced_entry_keeps_deadline_seniority():
    # a coalescing re-submit re-inserts at the dict tail but keeps the
    # ORIGINAL enqueue time; the deadline trigger must still see it as
    # the bucket's most-waited entry
    q = AdmissionQueue(1, max_pending=8, flush_docs=8,
                       flush_deadline_s=0.05)
    q.submit(0, "a", 3, now=0.0)            # bucket 4
    q.submit(0, "b", 3, now=0.04)           # bucket 4, younger
    q.submit(0, "a", 1, now=0.045)          # coalesce: a -> dict tail
    assert q.due(0.051) == [(0, 4, "deadline")]


def test_queue_coalesce_upgrades_to_urgent_class():
    q = AdmissionQueue(1, max_pending=8, flush_docs=4,
                       flush_deadline_s=0.05)
    q.qos = StubCtl({"interactive": 0.01, "bulk": 10.0})
    q.submit(0, "d", 1, now=0.0, qos="bulk")
    assert q.class_depth(0, "bulk") == 1
    # an interactive re-touch must not wait out the bulk deadline
    q.submit(0, "d", 1, now=0.0, qos="interactive")
    assert q.class_depth(0, "bulk") == 0
    assert q.class_depth(0, "interactive") == 1
    assert q.due(0.02) == [(0, 2, "deadline")]
    # the reverse direction never downgrades
    q.submit(0, "d", 1, now=0.0, qos="catchup")
    assert q.class_depth(0, "interactive") == 1


def test_queue_per_class_depth_budget():
    class Budgeted(StubCtl):
        def depth_budget(self, cls, max_pending):
            return 2 if cls == "bulk" else max_pending

    q = AdmissionQueue(1, max_pending=8, flush_docs=4,
                       flush_deadline_s=0.05)
    q.qos = Budgeted({"interactive": 0.05, "bulk": 0.5})
    q.submit(0, "b1", 1, now=0.0, qos="bulk")
    q.submit(0, "b2", 1, now=0.0, qos="bulk")
    with pytest.raises(Backpressure):
        q.submit(0, "b3", 1, now=0.0, qos="bulk")
    # the bulk budget must not take interactive admission down with it
    q.submit(0, "i1", 1, now=0.0, qos="interactive")


# ---- metrics + export surface --------------------------------------------

def test_merge_snapshots_sums_and_maxes():
    a, b = QosMetrics(), QosMetrics()
    a.bump_class("bulk", "admitted", 3)
    a.set_deadline("bulk", 0.4)
    b.bump_class("bulk", "admitted", 2)
    b.bump_class("bulk", "shed")
    b.set_deadline("bulk", 0.9)
    merged = merge_snapshots([a.snapshot(), None, b.snapshot()])
    assert merged["classes"]["bulk"]["admitted"] == 5
    assert merged["classes"]["bulk"]["shed"] == 1
    assert merged["classes"]["bulk"]["deadline_s"] == pytest.approx(0.9)
    assert merge_snapshots([None, None]) is None


def test_prom_qos_families_zero_filled_when_idle():
    clock = FakeClock()
    ctl, _q = make_controller(clock)
    text = render_metrics({"qos": ctl.export()})
    # an idle controller still exports every (key, class) series
    for key in QOS_CLASS_KEYS:
        for cls in QOS_CLASSES:
            assert f'dt_qos_{key}_total{{class="{cls}"}} 0' in text
    assert 'dt_qos_deadline_seconds{class="interactive"}' in text
    assert 'dt_qos_controller_total{decision="steps"} 0' in text
    assert "dt_qos_enabled 1" in text
    assert "dt_qos_mesh_state 0" in text
    # prom shape validity: one TYPE per family, no duplicate samples
    seen_types, seen_samples = set(), set()
    for line in text.strip().splitlines():
        if line.startswith("# TYPE"):
            fam = line.split()[2]
            assert fam not in seen_types
            seen_types.add(fam)
        elif not line.startswith("#"):
            key = line.rsplit(" ", 1)[0]
            assert key not in seen_samples, key
            seen_samples.add(key)


def test_scorecard_qos_block_optional_and_ungated():
    kw = dict(scenario={"name": "x"}, wall_s=1.0, virtual_s=1.0,
              totals={"ops": 10}, latency_p99_s={})
    plain = build_scorecard(**kw)
    assert "qos" not in plain
    snap = QosMetrics().snapshot()
    carded = build_scorecard(qos=snap, **kw)
    assert carded["qos"]["schema_version"] == 1
    # a qos block appearing on the new side must never gate a diff
    # against a pre-QoS baseline
    diff = diff_scorecards(plain, carded)
    assert diff["ok"], diff["regressions"]


# ---- end to end over HTTP ------------------------------------------------

def _post(base, doc, body=None, headers=None):
    payload = json.dumps(body or {"agent": "qa", "version": [],
                                  "ops": [{"kind": "ins", "pos": 0,
                                           "text": "hi "}]})
    req = urllib.request.Request(f"{base}/doc/{doc}/edit",
                                 data=payload.encode("utf8"))
    for k, v in (headers or {}).items():
        req.add_header(k, v)
    with urllib.request.urlopen(req, timeout=5) as r:
        return r.status, json.loads(r.read())


def test_server_shed_gate_and_debug_endpoint():
    from diamond_types_tpu.tools.server import serve
    srv = serve(port=0, data_dir=None, engine="host", serve_shards=2, qos=True)
    port = srv.server_address[1]
    base = f"http://127.0.0.1:{port}"
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        qctl = srv.store.scheduler.qos
        assert qctl is not None

        # healthy mesh: everything admits, the class rides the queue
        st, _ = _post(base, "t0-doc000")
        assert st == 200
        st, _ = _post(base, "t0-doc000", headers={"X-DT-QoS": "bulk"})
        assert st == 200

        # force the mesh gate to burning: bulk 429s with Retry-After,
        # interactive still lands — shed BEFORE interactive degrades
        qctl.force_mesh_state("burning", retry_after=1.5)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(base, "t0-doc001", headers={"X-DT-QoS": "bulk"})
        err = ei.value
        assert err.code == 429
        assert float(err.headers["Retry-After"]) == pytest.approx(1.5)
        detail = json.loads(err.read())
        assert detail["qos"] == "bulk"
        assert detail["reason"].startswith("mesh_burn")
        err.close()
        st, _ = _post(base, "t0-doc001")
        assert st == 200
        qctl.force_mesh_state(None)

        # /debug/qos + the /metrics qos block + prom render
        with urllib.request.urlopen(f"{base}/debug/qos",
                                    timeout=5) as r:
            dbg = json.loads(r.read())
        assert dbg["enabled"] and dbg["running"]
        assert dbg["classes"]["bulk"]["admitted"] >= 1
        assert dbg["classes"]["bulk"]["shed"] >= 1
        assert dbg["classes"]["interactive"]["shed"] == 0
        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as r:
            doc = json.loads(r.read())
        assert doc["qos"]["classes"]["interactive"]["admitted"] >= 2
        text = render_metrics(doc)
        assert 'dt_qos_shed_total{class="bulk"} ' in text
    finally:
        srv.shutdown()
        srv.server_close()


def test_server_qos_off_has_no_block():
    from diamond_types_tpu.tools.server import serve
    srv = serve(port=0, data_dir=None, engine="host", serve_shards=1)
    port = srv.server_address[1]
    base = f"http://127.0.0.1:{port}"
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        st, _ = _post(base, "t0-doc000", headers={"X-DT-QoS": "bulk"})
        assert st == 200
        with urllib.request.urlopen(f"{base}/metrics", timeout=5) as r:
            doc = json.loads(r.read())
        assert doc["qos"] is None
        with urllib.request.urlopen(f"{base}/debug/qos",
                                    timeout=5) as r:
            assert json.loads(r.read()) == {"enabled": False}
        assert "dt_qos_" not in render_metrics(doc)
    finally:
        srv.shutdown()
        srv.server_close()


# ---- scenario integration ------------------------------------------------

def test_smoke_scenario_with_qos_stamps_block():
    from diamond_types_tpu.workload import get_scenario
    from diamond_types_tpu.workload.runner import run_scenario
    card = run_scenario(get_scenario("smoke"), qos=True)
    assert card["ok"], card["slo"]
    qos = card["qos"]
    assert qos["schema_version"] == 1
    assert qos["classes"]["interactive"]["admitted"] > 0
    assert qos["classes"]["bulk"]["admitted"] > 0
    # a healthy smoke run never sheds
    assert all(row["shed"] == 0 for row in qos["classes"].values())
    assert qos["sheds_observed"] == 0
    assert qos["controller"]["steps"] > 0


@pytest.mark.slow
def test_flash_crowd_qos_ab_smoke():
    """A/B: adaptive admission on the QoS stressor must stay
    convergent and not regress against its own static control arm
    past the scorecard bands."""
    import dataclasses

    from diamond_types_tpu.workload import get_scenario
    from diamond_types_tpu.workload.runner import run_scenario
    sc = dataclasses.replace(get_scenario("flash-crowd"),
                             duration_s=8.0)
    control = run_scenario(sc)
    adaptive = run_scenario(sc, qos=True)
    assert "qos" not in control and adaptive["qos"] is not None
    assert adaptive["convergence"]["converged"]
    diff = diff_scorecards(control, adaptive)
    assert diff["ok"], diff["regressions"]
