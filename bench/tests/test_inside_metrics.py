"""The per-layer metrics that read a push's clocks outside any phase
(`bench/inside.py` and its readers): the handler
thread, what an accept finds waiting, the
interpreter's queue, the CPU by thread class and the flush worker's
pause. Found in the manifest BY NAME. Every reader returns
None on scrapes without the rows, which is every parent of the PR that
added them, and a number on scrapes with them. A CPU rehearsal proves
the rows and the arithmetic, nothing about the chip.

    python -m pytest bench/tests/test_inside_metrics.py -q -p no:cacheprovider
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import inside  # noqa: E402
from bench.run import metric_reader  # noqa: E402

CELLS = {"sat": ("b4-papers.edit-sat", "acked_edits_per_s"),
         "steady": ("b1-notes.edit-steady", "edit_ack_p50_ms"),
         "host4": ("host4-mixed.edit-sat128", "acked_edits_per_s"),
         "hunk": ("a2-sources.hunk-sat", "acked_edits_per_s")}
FRONT, SCHED, WHOLE = "HTTP front end", "scheduler", "whole served path"
# metric -> (cells, unit, better, source, layer)
TABLE = {
    "http.listen_waiting_share": (("sat", "host4", "hunk"),
                                  "%", "lower", "program_counter", FRONT),
    "http.thread_start_mean_ms": (("sat", "steady"),
                                  "ms", "lower", "program_span", FRONT),
    "http.thread_cpu_ms_a_request": (("sat", "host4"),
                                     "ms", "lower", "program_counter",
                                     FRONT),
    "loop.past_accept_share": (("sat", "host4", "hunk"),
                               "%", "higher", "program_span", WHOLE),
    "gil.wait_mean_ms": (("sat", "steady", "host4"),
                         "ms", "lower", "program_span", FRONT),
    "cpu.accept_loop_share": (("sat", "host4"),
                              "%", "lower", "program_counter", FRONT),
    "cpu.flush_workers_share": (("sat",),
                                "%", "lower", "program_counter", SCHED),
    "cpu.pump_share": (("host4",), "%", "lower", "program_counter", SCHED),
    "cpu.process_share": (("sat", "host4"),
                          "%", "lower", "program_counter", FRONT),
    "sched.pause_share": (("sat", "steady", "hunk"),
                          "%", "higher", "program_span", SCHED),
    "sched.flushes_per_s": (("sat",),
                            "calls/s", "lower", "program_counter", SCHED),
}
NAMES = [f"{base}.{cell}" for base, row in TABLE.items() for cell in row[0]]


def manifest() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        return json.load(f)


def test_the_manifest_names_every_one_with_reader_cell_and_layer():
    bench = manifest()
    by_name = {m["name"]: m for m in bench["per_layer"]}
    layers = {m["layer"] for m in bench["per_layer"]
              if m["name"] not in NAMES}
    cells = {w["name"] for w in bench["workloads"]}
    assert len(NAMES) == len(set(NAMES)) == 23
    for base, (where, unit, better, source, layer) in TABLE.items():
        for cell in where:
            name = f"{base}.{cell}"
            workload, moves = CELLS[cell]
            assert by_name[name] == {
                "name": name, "unit": unit, "better": better,
                "source": source, "layer": layer, "moves": moves,
                "workloads": [workload]}, name
            assert workload in cells and layer in layers
            assert os.path.exists(os.path.join(
                ROOT, "bench", "metrics", name + ".py")), name
    # the cell's own end-to-end metric is one it reports
    for m in bench["end_to_end"]:
        for name in NAMES:
            if by_name[name]["moves"] == m["name"]:
                assert by_name[name]["workloads"][0] in m["workloads"]


# ---- recorded scrapes --------------------------------------------------------

ROW = {"count": 0, "sum_s": 0.0, "max_s": 0.0, "lock_wait_s": 0.0}
CPU0 = {"process_s": 100.0, "accept_loop_s": 10.0, "pump_s": 4.0,
        "flush_workers_s": 2.0, "autosave_s": 1.0, "gil_probe_s": 0.1,
        "live_handlers_s": 30.0, "native_s": 20.0, "exited_s": 32.9}
CPU1 = dict(CPU0, process_s=190.0, accept_loop_s=35.0, pump_s=14.0,
            flush_workers_s=6.0, exited_s=80.0)


def row(count, sum_s, **counts):
    out = dict(ROW, count=count, sum_s=sum_s)
    if counts:
        out["counts"] = counts
    return out


def scrape(at, rows, cpu=None, serve=True):
    if not serve:
        return {"serve": {"version": 15}, "_at": at}
    block = {"version": 1, "locks": {}, "phases": rows}
    if cpu is not None:
        block["cpu"] = cpu
    return {"serve": {"phases": block}, "_at": at}


def ctx_of(m0, m1, clients=None, tiny=False):
    mix = {"loop": "closed"}
    if clients is not None:
        mix["clients"] = clients
    return {"m0": m0, "m1": m1, "seconds": 50.0, "mix": mix,
            "cell": {"name": "test.inside"},
            "device": {"rehearsal": "cpu"} if tiny else {},
            "config": {"fleet": [{"docs": 32}], "tiny": [{"docs": 4}]}}


# the parent of the PR: its rows, none of this PR's, no `cpu` block
OLD0 = {"http.edit": row(100, 0.2, lean=100),
        "http.accept_wait": row(100, 0.1), "sched.flush": row(40, 2.0)}
OLD1 = {"http.edit": row(1100, 2.4, lean=1100),
        "http.accept_wait": row(1100, 1.1), "sched.flush": row(290, 27.0)}
LISTEN0 = dict(listen_samples=3, listen_waiting=1)
LISTEN1 = dict(listen_samples=34, listen_waiting=25)
# the change: a window of 1,000 pushes in 50 s of traffic, scraped 100 s
# apart (a traced run's second scrape comes after the profiler's stop)
NEW0 = dict(OLD0, **{
    "sched.flush": row(40, 2.0, forced=40),
    "http.accept_wait": row(100, 0.1, **LISTEN0),
    "http.thread_start": row(12, 0.004), "http.thread_cpu": row(12, 0.012),
    "gil.wait": row(500, 0.05), "sched.pause": row(0, 0.0)})
NEW1 = dict(OLD1, **{
    "sched.flush": row(290, 27.0, forced=40, paced=250),
    "http.accept_wait": row(1100, 1.1, **LISTEN1),
    "http.thread_start": row(137, 0.0665),
    "http.thread_cpu": row(137, 0.187),
    "gil.wait": row(800, 10.05), "sched.pause": row(250, 20.0)})
WANT = {
    "http.listen_waiting_share": 100.0 * 24 / 31,
    "http.thread_start_mean_ms": 0.5,           # 125 sampled threads
    "http.thread_cpu_ms_a_request": 1.4,
    # 20 pushes/s x (1 + 2.2) ms = 0.064 clients of 32
    "loop.past_accept_share": 100.0 * 20 * 0.0032 / 32,
    # 10 s overslept; the 50 s of traffic hold (50 - 10) / 0.1 wakes
    "gil.wait_mean_ms": 10.0 / 400 * 1e3,
    "cpu.accept_loop_share": 50.0, "cpu.flush_workers_share": 8.0,
    "cpu.pump_share": 20.0, "cpu.process_share": 180.0,
    "sched.pause_share": 40.0, "sched.flushes_per_s": 5.0}


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_is_silent_without_the_rows_and_reads_them_with(name):
    # where a reader keeps the rows for PERF.md's tables
    # (`bench/out/test.inside.phases.json` here)
    os.makedirs(os.path.join(ROOT, "bench", "out"), exist_ok=True)
    read = metric_reader(name)
    # no clocks at all; the parent's clocks, on the parent's scrapes
    assert read(ctx_of(scrape(0.0, {}, serve=False),
                       scrape(100.0, {}, serve=False))) is None
    assert read(ctx_of(scrape(0.0, OLD0), scrape(100.0, OLD1))) is None
    got = read(ctx_of(scrape(0.0, NEW0, CPU0), scrape(100.0, NEW1, CPU1)))
    assert got == pytest.approx(WANT[name.rsplit(".", 1)[0]])


def test_the_loop_is_as_wide_as_the_generator_makes_it():
    m0, m1 = scrape(0.0, NEW0, CPU0), scrape(100.0, NEW1, CPU1)
    assert inside.clients(ctx_of(m0, m1)) == 32
    assert inside.clients(ctx_of(m0, m1, clients=128)) == 32
    assert inside.clients(ctx_of(m0, m1, clients=8)) == 8
    assert inside.clients(ctx_of(m0, m1, tiny=True)) == 4
    assert inside.past_accept_share(ctx_of(m0, m1, clients=8)) \
        == pytest.approx(4 * WANT["loop.past_accept_share"])


def test_a_worker_that_never_paused_reads_zero_not_nothing():
    rows0 = {k: v for k, v in NEW0.items() if k != "sched.pause"}
    rows1 = {k: v for k, v in NEW1.items() if k != "sched.pause"}
    ctx = ctx_of(scrape(0.0, rows0, CPU0), scrape(100.0, rows1, CPU1))
    assert inside.pause_share(ctx) == 0.0
    # a window with no sample of the listening socket has no share
    rows1["http.accept_wait"] = row(1100, 1.1, **LISTEN0)
    ctx = ctx_of(scrape(0.0, rows0, CPU0), scrape(100.0, rows1, CPU1))
    assert inside.listen_waiting_share(ctx) is None


def test_a_traced_rehearsal_prints_every_sat_metric():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "b4-papers.edit-sat",
         "--seed", "3000000019", "--seconds", "6", "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    got = out["metrics"]
    for name in NAMES:
        if name.endswith(".sat"):
            assert name in got and got[name]["value"] >= 0.0, name
    assert got["cpu.process_share.sat"]["value"] \
        > got["cpu.accept_loop_share.sat"]["value"] > 0.0
    assert got["http.thread_start_mean_ms.sat"]["value"] > 0.0
    assert got["sched.flushes_per_s.sat"]["value"] > 0.0
    with open(os.path.join(ROOT, "bench", "out",
                           "b4-papers.edit-sat.phases.json"),
              encoding="utf8") as f:
        kept = json.load(f)
    cpu = kept["m1"]["phases"]["cpu"]
    live = sum(v for k, v in cpu.items()
               if k not in ("process_s", "exited_s"))
    assert live + cpu["exited_s"] == pytest.approx(cpu["process_s"],
                                                   rel=0.02)
