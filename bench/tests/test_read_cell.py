#!/usr/bin/env python3
"""The read cell, `yb-pages.read95` (PR 44), found in the manifest by
name: its files letter for letter, its readers on a recorded set of
scrapes (a number where the program keeps the counts, None where it
does not: every parent of the PR that added them), a `--tiny` rehearsal
and two controls that must come out `correct: false`. A CPU rehearsal
proves the counts and the bytes, nothing about the chip.

    python -m pytest bench/tests/test_read_cell.py -q -p no:cacheprovider

The controls, in the manner of `bench/tests/controls.py`, are this file
run as a script (the harness must start before anything imports jax):

    python3 bench/tests/test_read_cell.py --hook <watch|behind|wrong_char> \\
        --workload yb-pages.read95 --seed <n> --seconds <s> --trace 0 [--tiny]

  watch       breaks nothing; before the server stops it prints the
              `http.get` root's counts (`{"get_counts": ...}`)
  behind      only forced flushes merge, so under traffic sessions stay
              behind their oplogs and reads fall to the host:
              `reads_from_host`
  wrong_char  every commit leaves the resident row's first character
              wrong and the lengths right: a GET's body no longer
              equals the reference as it arrives
              (`reads_vs_reference_mismatches`). At a host `GET` that
              check would pass unseen: the body comes from the chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "yb-pages.read95"
MOVES = "edit_ack_p50_ms"
# name -> (unit, better, source, layer)
METRICS = {
    "read.device_share": ("%", "higher", "program_counter", "read path"),
    "read.at_tip_share": ("%", "higher", "program_counter", "read path"),
    "read.get_mean_ms": ("ms", "lower", "program_span", "read path"),
    "read.sync_share": ("%", "lower", "program_span", "read path"),
    "read.fetch_mean_ms": ("ms", "lower", "program_span", "read path"),
    "lock.held_by_get_share": ("%", "lower", "program_span", "read path"),
    "tail.checkout_p50_ms": ("ms", "lower", "host_clock",
                             "whole served path"),
    "gen.late_p99_ms": ("ms", "lower", "host_clock", "load generator"),
    "compile.in_window": ("count", "lower", "program_counter", "compile"),
    "device.peak_hbm_bytes": ("bytes", "lower", "program_counter",
                              "device"),
    "device.replay_hbm_share": ("%", "higher", "device_trace", "kernels")}
# accepted readers of the edit path, autosave and the interpreter's
# queue (the layers `edit_ack_p50_ms` is made of), which list the cell
# after `b1-notes.edit-steady` (`tail.edit_ack_p95_ms` does not: a p95
# is given from 200 pushes on, bench/reduce.py, and a window of this
# cell has about 204)
ACCEPTED = ("http.lock_wait_share.steady", "http.edit_own_mean_ms.steady",
            "http.edit_len_hit_share.steady", "http.edit_handler_p50_ms",
            "gil.wait_mean_ms.steady",
            "sched.pause_share.steady", "sched.queue_wait_mean_ms.steady",
            "store.autosave_busy_share.steady",
            "store.autosave_max_ms.steady",
            "lock.held_by_autosave_share.steady")
# the readers of bench/reads.py: nothing on a program without the counts
NEW_COUNTS = ("read.device_share", "read.at_tip_share", "read.get_mean_ms",
              "read.sync_share", "read.fetch_mean_ms",
              "lock.held_by_get_share")


class Watch:
    def server_started(self, httpd) -> None:
        pass

    def before_shutdown(self, httpd) -> None:
        rows = httpd.store.obs.phases.snapshot()["phases"]
        print(json.dumps({"get_counts": rows["http.get"].get("counts", {}),
                          "get_checkout": rows.get("get.checkout", {})
                          .get("count", 0)}), flush=True)


class Behind(Watch):
    """A resident session moves only in a forced flush (the warm
    rounds', a drain's): under traffic the sessions stay behind their
    oplogs, a read's own flush included, so the read falls to the host
    and is counted."""

    def server_started(self, httpd) -> None:
        sched = httpd.store.scheduler
        inner = sched._flush_items

        def only_forced(shard, reason, items, min_fuse=2):
            # (the load's first flush of a document builds its session)
            if reason != "force" and all(
                    it.doc_id in sched.banks[shard].sessions
                    for it in items):
                return 0.0, 0.0
            return inner(shard, reason, items, min_fuse)

        sched._flush_items = only_forced


class WrongChar(Watch):
    """As `controls.ReplayWrongChar`: a commit leaves one character of
    the resident row wrong and every length right."""

    def server_started(self, httpd) -> None:
        from diamond_types_tpu.tpu import flush_fuse
        self._cls = flush_fuse.FusedDocSession
        self._commit = commit = self._cls.commit

        def broken_commit(sess, docs, lens, plan):
            return commit(sess, docs.at[0].add(1), lens, plan)

        self._cls.commit = broken_commit

    def before_shutdown(self, httpd) -> None:
        self._cls.commit = self._commit
        super().before_shutdown(httpd)


HOOKS = {"watch": Watch, "behind": Behind, "wrong_char": WrongChar}


def main() -> int:
    from bench import run
    argv = sys.argv[1:]
    i = argv.index("--hook")
    name, argv = argv[i + 1], argv[:i] + argv[i + 2:]
    rc, result = run.run_cli(argv, broken=HOOKS[name]())
    if result is None:
        print(json.dumps({"hook": name, "error": "no result line"}))
        return 2
    print(json.dumps(result), flush=True)
    return 0 if result["correct"] is (name == "watch") else 1


if __name__ == "__main__":
    sys.exit(main())


import pytest  # noqa: E402

from bench.run import find_cell, metric_reader  # noqa: E402


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        return json.load(f)


def load(path: str):
    with open(os.path.join(ROOT, path), encoding="utf8") as f:
        return json.load(f)


def test_the_cell_is_issue_44s_letter_for_letter(bench):
    cell, config, mix = find_cell(bench, CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "yb-pages", "read95", 1)
    assert len(cell["why"]) <= 200
    entry = next(c for c in bench["configs"] if c["name"] == "yb-pages")
    assert entry["file"] == "bench/configs/yb-pages.json"
    assert entry["reduced"] == ["fleet_docs"] == list(config["reduced"])
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert "YCSB" in entry["source"] and "workloadb" in entry["source"]
    assert config["name"] == "yb-pages" and config["architecture"] is None
    papers = load("bench/configs/b4-papers.json")
    (page,), (paper,) = config["fleet"], papers["fleet"]
    assert page == dict(paper, prefix="y", docs=128)
    assert config["fleet_docs"] == 128 and config["chips"] == 1
    # b4-papers' scheduler settings, and where a read is served: a
    # `MergeScheduler` argument the PR's parent does not have, so it
    # stops at `TypeError` before any load (bench/run.py hands
    # `sched_opts` to `serve()` unchanged)
    assert config["sched_opts"] == dict(papers["sched_opts"],
                                        reads="device")
    assert "reads" not in papers["sched_opts"]
    # the four guarantees word for word, and the fifth
    assert config["guarantees"][:4] == papers["guarantees"]
    assert len(config["guarantees"]) == 5
    assert "reads_from_host` is 0" in config["guarantees"][4]
    assert config["tiny"] == [dict(papers["tiny"][0], prefix="y", docs=8)]
    # YCSB-B, open loop
    assert (mix["loop"], mix["get_share"], mix["popularity"],
            mix["threads"]) == ("open", 0.95,
                                {"kind": "zipf", "s": 0.99}, 16)
    assert mix["burst"] == {"ops": 8, "mean_run": 14, "p_back": 0.425}
    assert (mix["warm_s"], mix["timeout_s"]) == (3.0, 60.0)
    assert mix["rate_per_s"] > 0 and mix["rate_per_s"] % 20 == 0
    steady = load("bench/mixes/edit-steady.json")
    assert mix["warm_shapes"] == steady["warm_shapes"]
    by_name = {m["name"]: m for m in bench["end_to_end"]}
    assert by_name[MOVES]["workloads"][-1] == CELL
    assert CELL not in by_name["acked_edits_per_s"]["workloads"]


def test_the_accepted_edit_path_readers_list_the_cell(bench):
    """The cell is judged by `edit_ack_p50_ms`: the accepted readers of
    the layers that metric is made of report in it too, each with the
    cell appended to its list and nothing else of it changed."""
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for name in ACCEPTED:
        m = by_name[name]
        assert m["workloads"] == ["b1-notes.edit-steady", CELL], name
        assert m["moves"] == MOVES, name
        assert metric_reader(name) is not None


def test_the_manifest_names_every_reader(bench):
    by_name = {m["name"]: m for m in bench["per_layer"]}
    for base, (unit, better, source, layer) in METRICS.items():
        name = base + ".read95"
        assert by_name[name] == {
            "name": name, "unit": unit, "better": better, "source": source,
            "layer": layer, "moves": MOVES, "workloads": [CELL]}, name
    assert {n for n in by_name if n.endswith(".read95")} \
        == {b + ".read95" for b in METRICS}
    # of what the benchmark had, the accepted edit-path readers alone
    assert sorted(m["name"] for m in bench["per_layer"]
                  if CELL in m.get("workloads", ())) \
        == sorted([b + ".read95" for b in METRICS] + list(ACCEPTED))


def ctx_with(rows0, rows1, **more):
    """Recorded scrapes, 10 s apart; GETs held the store lock for 0.2 s
    between them (sites `get.fetch`, `get.checkout`), edits for 1 s."""
    def serve(rows, k):
        if rows is None:
            return {"version": 15}
        return {"phases": {"version": 1, "phases": rows,
                           "locks": {"store.oplog": {
                               "get.fetch": {"hold_s": 0.1 * k},
                               "get.checkout": {"hold_s": 0.1 * k},
                               "edit.checkout": {"hold_s": 1.0 * k}}}}}
    ctx = {"m0": {"serve": serve(rows0, 1), "_at": 10.0},
           "m1": {"serve": serve(rows1, 2), "_at": 20.0},
           "cell": {"name": "recorded"}, "seconds": 10.0,
           "gen": {"late_ms": {"p99": 0.5}, "checkout_ms": {"p50": 1.25}},
           "compile_in_window": {"compiles": 0},
           "device": {"memory_peak_bytes": 1 << 28, "kind": "TPU v5 lite"},
           "peaks": {"TPU v5 lite": {"hbm_bytes_per_s": 819e9}},
           "spans": None, "trace": None}
    ctx.update(more)
    return ctx


def row(count=0, sum_s=0.0, **counts):
    out = {"count": count, "sum_s": sum_s, "lock_wait_s": 0.0}
    if counts:
        out["counts"] = counts
    return out


BEFORE = {"http.get": row(100, 0.1, lean=100, device=90, host=10,
                          at_tip=80),
          "get.sync": row(10, 0.02), "get.fetch": row(90, 0.03),
          "get.checkout": row(10, 0.01)}
AFTER = {"http.get": row(1100, 2.1, lean=1100, device=1040, host=60,
                         at_tip=890),
         "get.sync": row(70, 0.52), "get.fetch": row(1040, 0.98),
         "get.checkout": row(60, 0.06)}
# the parent: the root and the host's step, none of this PR's counts
PARENT0 = {"http.get": row(100, 0.1, lean=100),
           "get.checkout": row(100, 0.09)}
PARENT1 = {"http.get": row(1100, 2.1, lean=1100),
           "get.checkout": row(1100, 1.9)}


def quiet(rows):
    """Every read at the tip: no `get.sync` row at all."""
    return {k: v for k, v in rows.items() if k != "get.sync"}


@pytest.mark.parametrize("base", sorted(METRICS))
def test_a_reader_reads_a_number_and_nothing_without_the_counts(base):
    read = metric_reader(base + ".read95")
    trace = {"busy_s": 20.0, "window_s": 8.0}
    spans = {"replay": {"calls": 5, "bytes_needed": 2 * 5 * 4 * 262144}}
    got = read(ctx_with(BEFORE, AFTER, trace=trace, spans=spans))
    want = {"read.device_share": 95.0,
            "read.at_tip_share": 100.0 * 810 / 950,
            "read.get_mean_ms": 2.0, "read.sync_share": 25.0,
            "read.fetch_mean_ms": 1e3 * 0.95 / 950,
            "lock.held_by_get_share": 2.0,
            "tail.checkout_p50_ms": 1.25, "gen.late_p99_ms": 0.5,
            "compile.in_window": 0, "device.peak_hbm_bytes": 1 << 28,
            "device.replay_hbm_share": 100.0 * (
                2 * 5 * 4 * 262144 * 8.0 / 10.0) / (819e9 * 20.0)}
    assert got == pytest.approx(want[base])
    if base in NEW_COUNTS:
        assert read(ctx_with(PARENT0, PARENT1)) is None
        assert read(ctx_with(None, None)) is None
    if base == "read.sync_share":
        # the program counts and no read had to sync: 0, not nothing
        assert read(ctx_with(quiet(BEFORE), quiet(AFTER))) == 0
    if base == "device.replay_hbm_share":
        assert read(ctx_with(BEFORE, AFTER)) is None
        assert read(ctx_with(BEFORE, AFTER, trace=trace, spans=spans,
                             device={"rehearsal": "cpu"})) is None


def rehearse(hook: str, seconds: str = "6", trace: str = "1"):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "bench/tests/test_read_cell.py", "--hook", hook,
         "--workload", CELL, "--seed", "3000000019", "--seconds", seconds,
         "--trace", trace, "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    return p, json.loads(p.stdout.strip().splitlines()[-1])


def printed(p, key: str):
    return next(json.loads(line) for line in p.stdout.splitlines()
                if line.startswith('{"' + key + '"'))


def check_line(p, name: str) -> int:
    line = next(ln for ln in p.stdout.splitlines()
                if f"check {name}: " in ln)
    return int(line.split(f"check {name}: ")[1].split()[0])


def test_the_cell_rehearses_correct_and_every_get_is_the_devices():
    p, out = rehearse("watch")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert out["correct"] is True and out["failed"] == 0
    m = {k: v["value"] for k, v in out["metrics"].items()}
    for base in METRICS:
        # the rehearsal has no HBM to take a share of
        if base != "device.replay_hbm_share":
            assert base + ".read95" in m, base
    for name in ACCEPTED:
        assert name in m, name
    assert m["read.device_share.read95"] == 100.0
    assert 0 < m["read.at_tip_share.read95"] <= 100.0
    assert m["compile.in_window.read95"] == 0
    # every GET of the run, the warm-up's, the window's and the
    # verification's, was answered from a session
    with open(os.path.join(ROOT, "bench", "out",
                           f"{CELL}.3000000019.rows.json")) as f:
        sent = sum(json.load(f)["read"])
    verified = int(next(ln for ln in p.stdout.splitlines()
                        if "] verified " in ln).split("] verified ")[1]
                   .split()[0])
    seen = printed(p, "get_counts")
    assert sent > 100
    assert seen["get_counts"]["device"] == sent + verified
    assert "host" not in seen["get_counts"]
    assert check_line(p, "reads_from_host") == 0


def test_sessions_left_behind_fall_to_the_host_and_are_not_correct():
    p, out = rehearse("behind", trace="0")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert out["correct"] is False and out["failed"] == 0
    assert check_line(p, "reads_from_host") > 0
    # the host's answer is right, so nothing else sees it
    assert check_line(p, "reads_vs_reference_mismatches") == 0
    assert printed(p, "get_counts")["get_counts"]["host"] \
        == check_line(p, "reads_from_host")


def test_one_wrong_character_on_the_chip_reaches_the_reader():
    p, out = rehearse("wrong_char", trace="0")
    assert p.returncode == 0, p.stdout[-2000:] + p.stderr[-2000:]
    assert out["correct"] is False and out["failed"] == 0
    assert check_line(p, "reads_vs_reference_mismatches") > 0
    assert check_line(p, "reads_from_host") == 0
