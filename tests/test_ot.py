"""OT bridge conformance: the reference's golden vectors
(reference: test_data/ot/*.json, consumed by diamond-types-old
src/list/ot/ot.rs:294-307)."""

import json
import os

import pytest

from diamond_types_tpu.text import ot
from tests.conftest import REFERENCE_DIR, reference_path

if not os.path.isdir(REFERENCE_DIR):
    # the vectors are the upstream project's files: not in this repo, and
    # the container cannot fetch them (ROADMAP D12)
    pytest.skip(f"the reference's golden vectors are absent "
                f"({REFERENCE_DIR} does not exist)",
                allow_module_level=True)

DATA = reference_path("test_data", "ot")


def load(name):
    with open(os.path.join(DATA, name)) as f:
        return [json.loads(line) for line in f if line.strip()]


@pytest.mark.parametrize("i,case", list(enumerate(load("apply.json"))))
def test_apply_golden(i, case):
    assert ot.apply(case["str"], case["op"]) == case["result"]


@pytest.mark.parametrize("i,case", list(enumerate(load("compose.json"))))
def test_compose_golden(i, case):
    assert ot.compose(case["op1"], case["op2"]) == ot.normalize(case["result"])


@pytest.mark.parametrize("i,case", list(enumerate(load("transform.json"))))
def test_transform_golden(i, case):
    got = ot.transform(case["op"], case["otherOp"], case["side"])
    assert got == ot.normalize(case["result"])


def test_xf_stream_to_traversal():
    from diamond_types_tpu import OpLog
    ol = OpLog()
    a = ol.get_or_create_agent_id("alice")
    b = ol.get_or_create_agent_id("bob")
    ol.add_insert_at(a, [], 0, "hello world")
    v = ol.version
    ol.add_insert_at(a, v, 5, "!")
    ol.add_delete_at(b, v, 0, 5)
    trav = ot.xf_stream_to_traversal(ol.iter_xf_operations())
    assert ot.apply("", trav) == ol.checkout_tip().snapshot()

    # Incremental: a dumb client at `v` can catch up with one traversal op.
    trav2 = ot.xf_stream_to_traversal(
        ol.iter_xf_operations_from(v, ol.version))
    assert ot.apply("hello world", trav2) == ol.checkout_tip().snapshot()
