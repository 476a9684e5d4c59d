"""The four-chip cell, `host4-mixed.edit-sat128`, rehearsed on the CPU
with four virtual devices (`test_bench.py`'s `rehearse` drops
`XLA_FLAGS`, and one CPU device is fewer than the cell asks for), and
its readers: `bench/mesh.py` and the `.host4` files under
`bench/metrics/`. A CPU rehearsal proves nothing about the chip.

    python -m pytest bench/tests -q -p no:cacheprovider
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench.run import metric_reader  # noqa: E402

CELL = "host4-mixed.edit-sat128"
# what the rehearsal can read: a share of the HBM's peak needs a chip
NO_CHIP = {"device.mesh_replay_hbm_share.host4"}


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        return json.load(f)


def new_metrics(bench):
    return [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]


def test_the_manifest_has_the_cell_and_only_appends(bench):
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) \
        == (CELL, "host4-mixed", "edit-sat128", 4)
    assert bench["configs"][-1]["name"] == "host4-mixed"
    assert bench["configs"][-1]["reduced"] == ["fleet_docs"]
    acked = next(m for m in bench["end_to_end"]
                 if m["name"] == "acked_edits_per_s")
    assert acked["workloads"] == ["b4-papers.edit-sat", CELL]
    assert acked["bound"] == 0.1
    new = new_metrics(bench)
    assert len(new) == 15 and bench["per_layer"][-15:] == new
    for m in new:
        assert m["name"].endswith(".host4")
        assert m["moves"] == "acked_edits_per_s"
    with open(os.path.join(ROOT, "bench/configs/host4-mixed.json"),
              encoding="utf8") as f:
        cfg = json.load(f)
    assert cfg["architecture"] is None and cfg["sched_opts"]["mesh_window"]
    # stated, so that a program without the option refuses the
    # deployment at boot: the program's default on four shards
    assert cfg["sched_opts"]["mesh_window_rows"] \
        == 4 * cfg["sched_opts"]["flush_docs"]
    assert [(c["docs"], c["cap"], c["writers"]) for c in cfg["fleet"]] \
        == [(128, 262144, 2), (896, 16384, 1)]
    for name, cls in (("b4-papers", cfg["fleet"][0]),
                      ("b1-notes", cfg["fleet"][1])):
        with open(os.path.join(ROOT, f"bench/configs/{name}.json"),
                  encoding="utf8") as f:
            old = json.load(f)
        # the accepted fleets side by side: nothing but the count differs
        assert {k: v for k, v in old["fleet"][0].items() if k != "docs"} \
            == {k: v for k, v in cls.items() if k != "docs"}
        assert old["guarantees"] == cfg["guarantees"]
        assert old["sched_opts"] == {k: v for k, v in
                                     cfg["sched_opts"].items()
                                     if not k.startswith("mesh_window")}
    with open(os.path.join(ROOT, "bench/mixes/edit-sat128.json"),
              encoding="utf8") as f:
        mix = json.load(f)
    assert (mix["loop"], mix["clients"]) == ("closed", 128)
    with open(os.path.join(ROOT, "bench/mixes/edit-sat.json"),
              encoding="utf8") as f:
        assert mix["burst"] == json.load(f)["burst"]


def rehearse(trace: int):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELL, "--seed",
         "3000000019", "--seconds", "3", "--trace", str(trace), "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_rehearsal_untraced(bench):
    out = rehearse(0)
    assert set(out) == {"correct", "attempted", "failed", "metrics", "device"}
    assert out["correct"] is True and out["failed"] == 0
    assert out["device"]["count"] == 4
    assert out["device"]["rehearsal"].startswith("cpu --tiny")
    assert set(out["metrics"]) == {"acked_edits_per_s", "setup_s"}


def test_rehearsal_traced_reports_every_new_metric(bench):
    out = rehearse(1)
    assert out["correct"] is True and out["failed"] == 0
    allowed = {m["name"]: m["unit"] for m in bench["per_layer"]
               if CELL in m.get("workloads", [CELL])}
    assert set(out["metrics"]) <= set(allowed)
    for name, v in out["metrics"].items():
        assert v["unit"] == allowed[name]
    want = {m["name"] for m in new_metrics(bench)} - NO_CHIP
    assert want <= set(out["metrics"]), want - set(out["metrics"])
    got = {k: v["value"] for k, v in out["metrics"].items()}
    assert got["mesh.homes_off_bank.host4"] == 0
    assert got["sched.unmerged_ops_at_end.host4"] == 0
    assert 1 <= got["mesh.dispatches_per_window.host4"] <= 4
    for name in ("mesh.occupancy.host4", "mesh.rows_off_home_share.host4",
                 "mesh.arena_hit_share.host4", "mesh.stage_share.host4",
                 "mesh.fence_share.host4", "mesh.adopt_share.host4",
                 "lock.held_by_pump_share.host4"):
        assert 0 <= got[name] <= 100, name
    assert got["mesh.stage_share.host4"] + got["mesh.fence_share.host4"] \
        + got["mesh.adopt_share.host4"] < 100
    assert out["device"]["busy_s"] > 0


def load_reader(name: str):
    spec = importlib.util.spec_from_file_location(
        "reader_under_test", os.path.join(ROOT, "bench", "metrics",
                                          name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def block(at: float, rows: dict, locks: dict = None) -> dict:
    return {"_at": at, "serve": {"phases": {
        "version": 1, "phases": rows, "locks": locks or {},
        "slow_requests": 0}}}


def row(count, sum_s, **counts):
    out = {"count": count, "sum_s": sum_s, "lock_wait_s": 0.0,
           "lock_hold_s": 0.0}
    if counts:
        out["counts"] = counts
    return out


def mesh_row(count, sum_s, papers, notes, **more):
    more.update({"cap.262144.dispatches": papers[0],
                 "cap.262144.docs": papers[1],
                 "cap.262144.padded_rows": papers[2],
                 "cap.16384.dispatches": notes[0],
                 "cap.16384.docs": notes[1],
                 "cap.16384.padded_rows": notes[2]})
    return row(count, sum_s, **more)


def hand_made_ctx():
    """Ten windows between the scrapes: 10 dispatches of papers (70
    documents in 80 rows) and 10 of notes (300 in 320)."""
    m0 = block(100.0, {
        "sched.flush": row(5, 1.0, homes_off_bank=0),
        "mesh.replay": mesh_row(10, 0.5, (5, 30, 40), (5, 100, 160),
                                rows=130, rows_off_home=13, arena_hits=1,
                                arena_misses=9),
        "mesh.stage": row(10, 0.1), "mesh.fence": row(10, 0.2),
        "mesh.adopt": row(10, 0.1)},
        {"store.oplog": {"bank.plan": {"hold_s": 1.0},
                         "window.adopt": {"hold_s": 0.0},
                         "edit.checkout": {"hold_s": 3.0}}})
    m1 = block(151.0, {
        "sched.flush": row(15, 4.0, homes_off_bank=0),
        "mesh.replay": mesh_row(30, 2.5, (15, 100, 120), (15, 400, 480),
                                rows=500, rows_off_home=124, arena_hits=2,
                                arena_misses=28),
        "mesh.stage": row(30, 0.6), "mesh.fence": row(30, 1.0),
        "mesh.adopt": row(30, 0.4)},
        {"store.oplog": {"bank.plan": {"hold_s": 11.0},
                         "window.adopt": {"hold_s": 0.2},
                         "edit.checkout": {"hold_s": 4.0}}})
    return {"m0": m0, "m1": m1, "seconds": 51.0,
            "cell": {"name": CELL},
            "device": {"kind": "TPU v5 lite"},
            "peaks": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}},
            "trace": {"busy_s": 2.0, "window_s": 49.0, "chips": 4}}


def test_the_readers_on_a_hand_made_block(tmp_path, monkeypatch):
    from bench import mesh
    monkeypatch.setattr(mesh, "keep", lambda ctx: None)
    ctx = hand_made_ctx()
    assert mesh.by_class(ctx) == {
        262144: {"dispatches": 10, "docs": 70, "padded_rows": 80},
        16384: {"dispatches": 10, "docs": 300, "padded_rows": 320}}
    roof = load_reader("device.mesh_replay_hbm_share.host4")
    assert roof.replay_bytes(mesh.by_class(ctx)) \
        == 2 * 4 * (70 * 262144 + 300 * 16384) == 186122240
    # 186.1 MB, 49 of the traffic's 51 s, over 819 GB/s x 2 s x 4 chips
    assert roof.read(ctx) == pytest.approx(
        100 * 186122240 * (49 / 51) / (819e9 * 2.0 * 4))
    assert 0 < roof.read(ctx) < 100
    assert roof.read(dict(ctx, trace=None)) is None
    assert roof.read(dict(ctx, device={"kind": "cpu",
                                       "rehearsal": "cpu"})) is None
    want = {"mesh.dispatches_per_window.host4": 2.0,
            "mesh.occupancy.host4": 100 * 370 / 400,
            "mesh.rows_off_home_share.host4": 100 * 111 / 370,
            "mesh.arena_hit_share.host4": 100 * 1 / 20,
            "mesh.homes_off_bank.host4": 0,
            "mesh.window_mean_ms.host4": 300.0,
            "mesh.stage_share.host4": 25.0,
            "mesh.fence_share.host4": 40.0,
            "mesh.adopt_share.host4": 15.0,
            # bank.plan 10 s + window.adopt 0.2 s of 51 s; not the edits'
            "lock.held_by_pump_share.host4": 100 * 10.2 / 51}
    for name, value in want.items():
        assert metric_reader(name)(ctx) == pytest.approx(value), name


def test_every_new_reader_finds_nothing_in_a_program_without_the_rows(
        bench, monkeypatch):
    """The parent of the PR that added them: `sched.flush` and the lock
    sites are there (PR 25), `mesh.replay` and the counts are not."""
    from bench import mesh
    monkeypatch.setattr(mesh, "keep", lambda ctx: None)
    ctx = hand_made_ctx()
    for m in ("m0", "m1"):
        rows = ctx[m]["serve"]["phases"]["phases"]
        ctx[m]["serve"]["phases"]["phases"] = {
            "sched.flush": {k: v for k, v in rows["sched.flush"].items()
                            if k != "counts"}}
    assert mesh.by_class(ctx) is None
    reads_the_parent = {"mesh.window_mean_ms.host4",
                        "lock.held_by_pump_share.host4"}
    program = [m["name"] for m in new_metrics(bench)
               if m["source"] in ("program_span", "program_counter")
               and m["name"].startswith(("mesh.", "lock."))] \
        + ["device.mesh_replay_hbm_share.host4"]
    assert len(program) == 11
    for name in program:
        v = metric_reader(name)(ctx)
        if name in reads_the_parent:
            assert v is not None, name
        else:
            assert v is None, name
    # and with no clocks at all (before PR 25) every one is silent
    for m in ("m0", "m1"):
        ctx[m]["serve"].pop("phases")
    for name in program:
        assert metric_reader(name)(ctx) is None, name
