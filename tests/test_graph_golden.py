"""Conformance tests against the reference's implementation-independent DAG
algorithm vectors (reference: test_data/causal_graph/*.json, generated by the
reference itself under its gen_test_data feature — see SURVEY.md §4.3).
"""

import json
import os

import pytest

from diamond_types_tpu.causalgraph.graph import Graph, DiffFlag, ROOT
from tests.conftest import REFERENCE_DIR, reference_path

if not os.path.isdir(REFERENCE_DIR):
    # the vectors are the upstream project's files: not in this repo, and
    # the container cannot fetch them (ROADMAP D12)
    pytest.skip(f"the reference's golden vectors are absent "
                f"({REFERENCE_DIR} does not exist)",
                allow_module_level=True)

DATA = reference_path("test_data", "causal_graph")


def build_graph(hist):
    g = Graph()
    for e in hist:
        g.push(e["parents"], e["span"][0], e["span"][1])
    return g


def load_cases(name):
    path = os.path.join(DATA, name)
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.mark.parametrize("case", load_cases("diff.json"))
def test_diff_golden(case):
    g = build_graph(case["hist"])
    only_a, only_b = g.diff_rev(case["a"], case["b"])
    assert only_a == [tuple(s) for s in case["expect_a"]]
    assert only_b == [tuple(s) for s in case["expect_b"]]
    # Slow path must agree with the fast paths.
    sa, sb = g._diff_slow(case["a"], case["b"])
    assert (sa, sb) == (only_a, only_b)


@pytest.mark.parametrize("case", load_cases("version_contains.json"))
def test_version_contains_golden(case):
    g = build_graph(case["hist"])
    target = case["target"]
    if target == -1:
        target = ROOT
    assert g.frontier_contains_version(case["frontier"], target) == case["expected"]


FLAGS = {"OnlyA": DiffFlag.ONLY_A, "OnlyB": DiffFlag.ONLY_B, "Shared": DiffFlag.SHARED}


def collect_conflicting(g, a, b, slow):
    spans = []

    def visit(span, flag):
        assert span[1] > span[0]
        # Merge with previous span when contiguous (we visit in reverse order).
        if spans and spans[-1][1] == flag and span[1] == spans[-1][0][0]:
            spans[-1] = ((span[0], spans[-1][0][1]), flag)
        else:
            spans.append((span, flag))

    if slow:
        common = g._find_conflicting_slow(a, b, visit)
    else:
        common = g.find_conflicting(a, b, visit)
    return common, spans


@pytest.mark.parametrize("slow", [False, True])
@pytest.mark.parametrize("case", load_cases("conflicting.json"))
def test_conflicting_golden(case, slow):
    g = build_graph(case["hist"])
    if slow and list(case["a"]) == list(case["b"]):
        pytest.skip("slow path requires a != b")
    common, spans = collect_conflicting(g, case["a"], case["b"], slow)
    expect = [((s["start"], s["end"]), FLAGS[f]) for s, f in case["expect_spans"]]
    expect.reverse()  # golden spans are ascending; visits are descending
    assert spans == expect
    assert common == case["expect_common"]


def test_version_cmp_basic():
    g = build_graph([
        {"span": [0, 3], "parents": []},
        {"span": [3, 6], "parents": []},
        {"span": [6, 9], "parents": [2, 5]},
    ])
    assert g.version_cmp(0, 2) == -1
    assert g.version_cmp(2, 0) == 1
    assert g.version_cmp(1, 1) == 0
    assert g.version_cmp(1, 4) is None
    assert g.version_cmp(8, 0) == 1
    assert g.version_cmp(3, 8) == -1


def test_dominators_and_union():
    g = build_graph([
        {"span": [0, 3], "parents": []},
        {"span": [3, 6], "parents": []},
        {"span": [6, 9], "parents": [2, 5]},
    ])
    assert g.find_dominators([0, 1, 2]) == [2]
    assert g.find_dominators([2, 5]) == [2, 5]
    assert g.find_dominators([2, 5, 8]) == [8]
    assert g.version_union([2], [5]) == [2, 5]
    assert g.version_union([8], [5]) == [8]
    assert g.find_dominators_2([2], [5]) == [2, 5]
    assert g.find_dominators_2([8], [2, 5]) == [8]


def test_frontier_advance_retreat():
    g = build_graph([
        {"span": [0, 3], "parents": []},
        {"span": [3, 6], "parents": []},
        {"span": [6, 9], "parents": [2, 5]},
    ])
    f = []
    g.advance_frontier(f, (0, 3))
    assert f == [2]
    g.advance_frontier(f, (3, 6))
    assert f == [2, 5]
    g.advance_frontier(f, (6, 9))
    assert f == [8]
    g.retreat_frontier(f, (6, 9))
    assert f == [2, 5]
    g.retreat_frontier(f, (3, 6))
    assert f == [2]
    g.retreat_frontier(f, (0, 3))
    assert f == []
