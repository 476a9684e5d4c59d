"""`http.pooled_share.sat` / `.steady` / `.host4` / `.hunk` and
`cpu.http_workers_share.sat` / `.host4`: how often a connection met a
parked resident handler thread, and what those threads cost
(`pooled` / `born` on the `http.accept_wait` row's counts,
`http_workers_s` in the `cpu` block; bench/pool.py). Found in the
manifest BY NAME. None on a program without the counts or the class,
which is every parent of the PR that added them. A CPU rehearsal proves
the counts and the arithmetic, nothing about the chip.

    python -m pytest bench/tests/test_pool_metrics.py -q -p no:cacheprovider
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

from bench.run import metric_reader  # noqa: E402

CELLS = {"sat": ("b4-papers.edit-sat", "acked_edits_per_s"),
         "steady": ("b1-notes.edit-steady", "edit_ack_p50_ms"),
         "host4": ("host4-mixed.edit-sat128", "acked_edits_per_s"),
         "hunk": ("a2-sources.hunk-sat", "acked_edits_per_s")}
# metric -> (cells, better)
TABLE = {"http.pooled_share": (("sat", "steady", "host4", "hunk"), "higher"),
         "cpu.http_workers_share": (("sat", "host4"), "lower")}
NAMES = [f"{base}.{cell}" for base, row in TABLE.items() for cell in row[0]]


def test_the_manifest_names_all_six():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf8") as f:
        bench = json.load(f)
    by_name = {m["name"]: m for m in bench["per_layer"]}
    assert len(NAMES) == 6
    for base, (where, better) in TABLE.items():
        for cell in where:
            name = f"{base}.{cell}"
            workload, moves = CELLS[cell]
            assert by_name[name] == {
                "name": name, "unit": "%", "better": better,
                "source": "program_counter", "layer": "HTTP front end",
                "moves": moves, "workloads": [workload]}, name
            assert os.path.exists(os.path.join(
                ROOT, "bench", "metrics", name + ".py")), name
    for m in bench["end_to_end"]:
        for name in NAMES:
            if by_name[name]["moves"] == m["name"]:
                assert by_name[name]["workloads"][0] in m["workloads"]


# ---- recorded scrapes --------------------------------------------------------

def accept_row(count, **counts):
    out = {"count": count, "sum_s": count * 1e-3, "max_s": 0.0,
           "lock_wait_s": 0.0}
    if counts:
        out["counts"] = counts
    return out


def ctx_of(row0, row1, cpu0=None, cpu1=None):
    def scrape(at, row, cpu):
        if row is None:
            return {"serve": {"version": 15}, "_at": at}
        block = {"version": 1, "locks": {},
                 "phases": {"http.accept_wait": row}}
        if cpu is not None:
            block["cpu"] = cpu
        return {"serve": {"phases": block}, "_at": at}
    return {"m0": scrape(0.0, row0, cpu0), "m1": scrape(100.0, row1, cpu1),
            "seconds": 50.0}


# the parent: the row, the listening socket's samples, no pool
OLD0 = accept_row(100, listen_samples=3, listen_waiting=1)
OLD1 = accept_row(1100, listen_samples=34, listen_waiting=25)
CPU_OLD0 = {"process_s": 100.0, "accept_loop_s": 10.0, "exited_s": 40.0}
CPU_OLD1 = {"process_s": 190.0, "accept_loop_s": 35.0, "exited_s": 90.0}
WANT = {"http.pooled_share": 100.0 * 750 / 1000,
        "cpu.http_workers_share": 100.0 * 30.0 / 50.0}


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_is_silent_without_the_counts_and_reads_them_with(name):
    read = metric_reader(name)
    # no clocks at all; the parent's rows and `cpu` block
    assert read(ctx_of(None, None)) is None
    assert read(ctx_of(OLD0, OLD1, CPU_OLD0, CPU_OLD1)) is None
    got = read(ctx_of(
        accept_row(100, pooled=64, **OLD0["counts"]),
        accept_row(1100, pooled=814, born=250, **OLD1["counts"]),
        dict(CPU_OLD0, http_workers_s=2.0),
        dict(CPU_OLD1, http_workers_s=32.0)))
    assert got == pytest.approx(WANT[name.rsplit(".", 1)[0]])


def test_a_window_that_only_bore_threads_reads_zero_and_an_empty_one_nothing():
    read = metric_reader("http.pooled_share.sat")
    assert read(ctx_of(accept_row(100, pooled=64),
                       accept_row(1100, pooled=64, born=1000))) == 0.0
    # every connection pooled: `born` was never written
    assert read(ctx_of(accept_row(100, pooled=64),
                       accept_row(1100, pooled=1064))) == 100.0
    assert read(ctx_of(accept_row(100, pooled=64, born=3),
                       accept_row(100, pooled=64, born=3))) is None


def test_a_traced_rehearsal_prints_the_steady_cells_share():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload",
         "b1-notes.edit-steady", "--seed", "3000000019", "--seconds", "6",
         "--trace", "1", "--tiny"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True and out["failed"] == 0
    share = out["metrics"]["http.pooled_share.steady"]
    assert share["unit"] == "%" and 50.0 <= share["value"] <= 100.0
