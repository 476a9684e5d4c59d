"""What `bench/` reaches for in the program BY NAME, pinned here so a
change to the flush path cannot silence the benchmark without a test
failing first (ISSUE 29, "Hold fixed").

`bench/instrument.py` puts its clocks in `flush_fuse.fused_replay`'s and
`FusedDocSession.plan_tail`'s place AFTER the server has started;
`bench/run.py` hands every configuration's `sched_opts` to
`MergeScheduler`, reads `metrics_json()` by key and waits on a few of
the scheduler's attributes. One parametrised test, a case a name; the
files under `bench/` are read, never written.
"""

import json
import os
import random

import pytest

from diamond_types_tpu.serve.admission import PendingMerge
from diamond_types_tpu.serve.scheduler import MergeScheduler
from diamond_types_tpu.text.oplog import OpLog
from diamond_types_tpu.tpu import flush_fuse as ff

pytestmark = [pytest.mark.fused, pytest.mark.serve]

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FUSED_OPTS = {"cap": 256, "max_ins": 4}
DOCS = ("c0", "c1", "c2")


def _sched(ols, n_shards=1, **kw):
    kw.setdefault("fused_opts", FUSED_OPTS)
    return MergeScheduler(n_shards, resolve=lambda d: ols[d],
                          engine="device", flush_docs=8,
                          flush_deadline_s=10.0, flush_workers=False, **kw)


def _round(sched, ols, rng) -> None:
    """Every document takes an insert, then one forced flush."""
    for d in DOCS:
        ol = ols.setdefault(d, OpLog())
        ol.doc_id = d
        a = ol.get_or_create_agent_id("a")
        n = len(ol.checkout_tip().snapshot())
        ol.add_insert(a, rng.randint(0, n), "abc")
        assert sched.submit(d, n_ops=1)["accepted"]
    sched.pump(force=True)


def _flushed_sched():
    """A scheduler whose second flush replayed the three documents."""
    ols, rng = {}, random.Random(29)
    sched = _sched(ols)
    _round(sched, ols, rng)         # builds the sessions at the tip
    _round(sched, ols, rng)         # three tails, one replay
    return sched, ols, rng


# ---- the wrappers are the ones called ---------------------------------------

def _replaced_fused_replay(monkeypatch):
    sched, ols, rng = _flushed_sched()
    seen = []
    real = ff.fused_replay
    # put in place AFTER the scheduler was built and has flushed, as
    # bench/instrument.py does: a hoisted import would miss it
    monkeypatch.setattr(
        ff, "fused_replay",
        lambda sessions, plans: seen.append(len(sessions))
        or real(sessions, plans))
    _round(sched, ols, rng)
    assert seen == [len(DOCS)]
    for d in DOCS:
        assert sched.text(d) == ols[d].checkout_tip().snapshot()


def _replaced_plan_tail(monkeypatch):
    sched, ols, rng = _flushed_sched()
    seen = []
    real = ff.FusedDocSession.plan_tail
    monkeypatch.setattr(
        ff.FusedDocSession, "plan_tail",
        lambda sess: seen.append(sess.oplog.doc_id) or real(sess))
    _round(sched, ols, rng)
    assert sorted(seen) == sorted(DOCS)


def _fused_jit_cache_key(_monkeypatch):
    sched, _ols, _rng = _flushed_sched()
    caps = {s.cap for s in sched.banks[0].sessions.values()}
    keys = list(ff._fused_jit_cache)
    assert keys and all(
        isinstance(k, tuple) and len(k) == 4
        and all(isinstance(x, int) for x in k) for k in keys)
    # (b, n, mi, cap): the three documents went out as one batch (of 4,
    # or of a larger class another test of this process left warm)
    assert any(b >= len(DOCS) and mi == FUSED_OPTS["max_ins"]
               and cap in caps for b, _n, mi, cap in keys)


# ---- metrics_json() by key, the scheduler by attribute ----------------------

METRIC_PATHS = (
    [("totals", k) for k in ("flushed_ops", "reads_from_host",
                             "host_fallbacks", "device_errors",
                             "warmup_errors", "pump_errors")]
    + [("fused", "docs"), ("fused", "device_calls"), ("window",),
       ("router_counts",), ("phases",)])


def _metric(path):
    def case(_monkeypatch):
        from diamond_types_tpu.obs import Observability
        ols, rng = {}, random.Random(29)
        sched = _sched(ols)
        sched.attach_obs(Observability())    # `phases` rides on it
        _round(sched, ols, rng)
        _round(sched, ols, rng)
        node = sched.metrics_json()
        for key in path:
            assert key in node, path
            node = node[key]
        if path == ("totals", "flushed_ops"):
            assert node == 2 * len(DOCS)
        elif path == ("fused", "docs"):
            assert node == len(DOCS)
        elif path == ("fused", "device_calls"):
            assert node == 1
        elif path[0] == "totals":
            assert node == 0
        elif path == ("router_counts",):
            assert sum(node) == len(DOCS)
        elif path == ("phases",):
            assert "sched.flush" in node["phases"]
    return case


def _attr_banks(_monkeypatch):
    sched, _ols, _rng = _flushed_sched()
    sessions = {}
    for bank in sched.banks:
        sessions.update(bank.sessions)
    assert sorted(sessions) == sorted(DOCS)
    assert all(isinstance(s, ff.FusedDocSession) and s.cap and s.merges
               for s in sessions.values())


def _attr_queue_total_depth(_monkeypatch):
    ols = {"q": OpLog()}
    sched = _sched(ols)
    assert sched.queue.total_depth() == 0
    assert sched.submit("q", n_ops=1)["accepted"]
    assert sched.queue.total_depth() == 1


def _attr_idle_cv_and_inflight(_monkeypatch):
    sched, _ols, _rng = _flushed_sched()
    with sched._idle_cv:
        assert sched._inflight == 0


# ---- every configuration's sched_opts, as bench/run.py extends them ---------

def _config_files():
    return sorted(f for f in os.listdir(os.path.join(ROOT, "bench",
                                                     "configs"))
                  if f.endswith(".json"))


def _config(fname):
    def case(_monkeypatch):
        from bench import run
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            manifest = json.load(f)
        with open(os.path.join(ROOT, "bench", "configs", fname)) as f:
            config = json.load(f)
        chips = {w["chips"] for w in manifest["workloads"]
                 if w["config"] == config["name"]}
        assert len(chips) == 1, (fname, chips)
        chips = chips.pop()
        so = run.sched_opts(config, config["tiny"], chips)
        # serve() adds these two for a device engine
        so.setdefault("place_on_devices", True)
        so.setdefault("warmup", False)
        ols = {}
        sched = MergeScheduler(chips, resolve=lambda d: ols[d],
                               engine="device", **so)
        assert len(sched.banks) == chips
        assert sched.mesh_window == bool(
            config["sched_opts"].get("mesh_window"))
        assert all(b.fused_opts == so["fused_opts"] for b in sched.banks)
        # and a document goes through it
        ol = ols["x"] = OpLog()
        ol.doc_id = "x"
        ol.add_insert(ol.get_or_create_agent_id("a"), 0, "text")
        shard = sched.router.shard_of("x")
        sched.banks[shard].sync_docs([PendingMerge("x", 1, 0.0)],
                                     ols.__getitem__)
        assert sched.text("x") == "text"
        sched.stop_workers()
    return case


CASES = {
    "replaced_fused_replay_is_called": _replaced_fused_replay,
    "replaced_plan_tail_is_called": _replaced_plan_tail,
    "fused_jit_cache_key_b_n_mi_cap": _fused_jit_cache_key,
    "sched.banks": _attr_banks,
    "sched.queue.total_depth": _attr_queue_total_depth,
    "sched._idle_cv+_inflight": _attr_idle_cv_and_inflight,
}
CASES.update({"metrics_json:" + ".".join(p): _metric(p)
              for p in METRIC_PATHS})
CASES.update({"sched_opts:" + f: _config(f) for f in _config_files()})


@pytest.mark.parametrize("case", sorted(CASES))
def test_what_the_benchmark_reaches_for_by_name(case, monkeypatch):
    CASES[case](monkeypatch)
