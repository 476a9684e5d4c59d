"""Device-tier tests on the virtual 8-device CPU mesh (conftest sets
JAX_PLATFORMS=cpu + xla_force_host_platform_device_count=8)."""

import json
import os

import numpy as np
import pytest

from diamond_types_tpu.causalgraph.graph import Graph
from tests.conftest import reference_path

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from diamond_types_tpu.tpu import graph_kernels as gk  # noqa: E402
from diamond_types_tpu.tpu.batch import (docs_to_strings, encode_trace_ops,  # noqa: E402
                                         replay_batch)


def build_graph(hist):
    g = Graph()
    for e in hist:
        g.push(e["parents"], e["span"][0], e["span"][1])
    return g


def load_cases(name):
    path = os.path.join(reference_path("test_data", "causal_graph"), name)
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def test_device_contains_matches_golden_vectors():
    cases = load_cases("version_contains.json")
    # Group by identical graph to batch queries.
    by_hist = {}
    for c in cases:
        by_hist.setdefault(json.dumps(c["hist"]), []).append(c)
    for hist_s, group in by_hist.items():
        g = build_graph(json.loads(hist_s))
        fn = gk.make_contains_fn(g)
        k = max(len(c["frontier"]) for c in group) or 1
        frontiers = np.full((len(group), k), -1, dtype=np.int32)
        targets = np.zeros((len(group),), dtype=np.int32)
        for i, c in enumerate(group):
            for j, v in enumerate(c["frontier"]):
                frontiers[i, j] = v
            targets[i] = c["target"] if c["target"] != -1 else -1
        got = np.asarray(fn(jnp.asarray(frontiers), jnp.asarray(targets)))
        for i, c in enumerate(group):
            assert bool(got[i]) == c["expected"], (c, bool(got[i]))


def test_device_diff_matches_host():
    cases = load_cases("diff.json")
    for c in cases:
        g = build_graph(c["hist"])
        packed = gk.pack_graph(g)
        k = max(len(c["a"]), len(c["b"]), 1)

        def pad(f):
            return jnp.asarray(np.array(f + [-1] * (k - len(f)), dtype=np.int32))

        ra, rb = gk.diff_masks(packed, pad(list(c["a"])), pad(list(c["b"])))
        ra, rb = np.asarray(ra), np.asarray(rb)
        # only_a = covered by a but not b, per run
        only_a, only_b = [], []
        for i in range(len(g.starts)):
            s = g.starts[i]
            a_hi, b_hi = int(ra[i]), int(rb[i])
            if a_hi > b_hi:
                lo = max(s, b_hi + 1)
                if only_a and only_a[-1][1] == lo:
                    only_a[-1] = (only_a[-1][0], a_hi + 1)
                else:
                    only_a.append((lo, a_hi + 1))
            elif b_hi > a_hi:
                lo = max(s, a_hi + 1)
                if only_b and only_b[-1][1] == lo:
                    only_b[-1] = (only_b[-1][0], b_hi + 1)
                else:
                    only_b.append((lo, b_hi + 1))
        ea, eb = g.diff(c["a"], c["b"])
        assert only_a == ea, (c, only_a, ea)
        assert only_b == eb


def test_batched_replay_matches_rope():
    from diamond_types_tpu.text.trace import TestData, replay_direct
    txns = [[(0, 0, "hello world")], [(5, 6, "")], [(5, 0, ", there")],
            [(0, 1, "H")], [(12, 0, "!")]]
    data = TestData("", "", txns)
    expected = replay_direct(data)

    pos, dl, il, chars = encode_trace_ops(txns, max_ins=16)
    b = 8
    docs, lens = replay_batch(
        jnp.asarray(np.tile(pos, (b, 1))), jnp.asarray(np.tile(dl, (b, 1))),
        jnp.asarray(np.tile(il, (b, 1))),
        jnp.asarray(np.tile(chars, (b, 1, 1))), cap=64)
    out = docs_to_strings(np.asarray(docs), np.asarray(lens))
    assert all(s == expected for s in out)


def test_sharded_replay_8_devices():
    from diamond_types_tpu.parallel.mesh import make_mesh, sharded_replay
    assert len(jax.devices()) >= 8, "conftest must force 8 cpu devices"
    mesh = make_mesh(8)
    txns = [[(0, 0, "abcdef")], [(2, 2, "XY")], [(0, 1, "")]]
    pos, dl, il, chars = encode_trace_ops(txns, max_ins=8)
    b = 16
    docs, lens = sharded_replay(
        mesh, np.tile(pos, (b, 1)), np.tile(dl, (b, 1)),
        np.tile(il, (b, 1)), np.tile(chars, (b, 1, 1)), cap=32)
    out = docs_to_strings(np.asarray(docs), np.asarray(lens))
    assert all(s == "bXYef" for s in out), out


def test_sharded_graph_propagation():
    from diamond_types_tpu.parallel.mesh import (make_mesh, pad_edges,
                                                 sharded_reach_fixed_point)
    # Fan-in DAG: 16 root runs all merged by one run.
    g = Graph()
    for i in range(16):
        g.push([], i * 10, i * 10 + 10)
    g.push([i * 10 + 9 for i in range(16)], 160, 170)
    packed = gk.pack_graph(g)
    n = packed["n"]
    src, plv, prun = pad_edges(packed, 8)
    reach0 = np.full((n,), -1, dtype=np.int32)
    reach0[16] = 169  # frontier at the merge tip

    mesh = make_mesh(8, axis="graph")
    reach = np.asarray(sharded_reach_fixed_point(
        mesh, packed["starts"], jnp.asarray(src), jnp.asarray(plv),
        jnp.asarray(prun), jnp.asarray(reach0)))
    # Every root run must be fully covered.
    assert all(reach[i] == i * 10 + 9 for i in range(16)), reach[:17]


def _fanin_graph(n_replicas: int, run_len: int = 8):
    """BASELINE config 5 shape: n_replicas concurrent root runs, one
    fan-in merge tip naming every replica's last LV as a parent."""
    g = Graph()
    for i in range(n_replicas):
        g.push([], i * run_len, (i + 1) * run_len)
    tip = n_replicas * run_len
    g.push([(i + 1) * run_len - 1 for i in range(n_replicas)], tip, tip + 4)
    return g, tip


def test_sharded_10k_replica_fanin():
    """The 10k-replica fan-in graph (BASELINE config 5) on the 8-device
    mesh: 10k edges shard evenly (edge-parallel CSR — the round-1 dense
    [n, max_parents] layout was O(n * 10k) memory and could not run)."""
    from diamond_types_tpu.parallel.mesh import (make_mesh, pad_edges,
                                                 sharded_reach_fixed_point)
    n_rep = 10_000
    g, tip = _fanin_graph(n_rep)
    packed = gk.pack_graph(g)
    assert packed["m"] == n_rep
    n = packed["n"]
    src, plv, prun = pad_edges(packed, 8)
    reach0 = np.full((n,), -1, dtype=np.int32)
    reach0[n - 1] = tip + 3

    mesh = make_mesh(8, axis="graph")
    reach = np.asarray(sharded_reach_fixed_point(
        mesh, packed["starts"], jnp.asarray(src), jnp.asarray(plv),
        jnp.asarray(prun), jnp.asarray(reach0)))
    assert (reach[:n_rep] == np.arange(1, n_rep + 1) * 8 - 1).all()

    # single-chip kernel agrees
    reach1 = np.asarray(gk.reach_fixed_point(
        packed, jnp.asarray(reach0)))
    assert (reach1 == reach).all()


def test_pallas_replay_matches_xla_path():
    """Pallas step kernel (interpret mode on CPU) vs the XLA replay path."""
    from diamond_types_tpu.tpu.pallas_kernels import replay_batch_pallas
    txns = [[(0, 0, "hello world")], [(5, 6, "")], [(5, 0, ", there")],
            [(0, 1, "H")], [(12, 0, "!")]]
    pos, dl, il, chars = encode_trace_ops(txns, max_ins=16)
    b = 4
    args = (jnp.asarray(np.tile(pos, (b, 1))), jnp.asarray(np.tile(dl, (b, 1))),
            jnp.asarray(np.tile(il, (b, 1))),
            jnp.asarray(np.tile(chars, (b, 1, 1))))
    ref_docs, ref_lens = replay_batch(*args, cap=128)
    docs, lens = replay_batch_pallas(*args, cap=128, interpret=True)
    assert np.array_equal(np.asarray(docs), np.asarray(ref_docs))
    assert np.array_equal(np.asarray(lens), np.asarray(ref_lens))


def test_replay_long_deletes_split_to_bound():
    """Deletes longer than max_ins exercise encode_trace_ops' split loop
    and the shift == -max_ins extreme of the static-roll select."""
    from diamond_types_tpu.text.trace import TestData, replay_direct
    txns = [[(0, 0, "hello there world")], [(5, 9, "")], [(0, 0, ">>")],
            [(2, 7, "")], [(0, 0, "ab")]]
    data = TestData("", "", txns)
    expected = replay_direct(data)

    for max_ins in (2, 4):
        pos, dl, il, chars = encode_trace_ops(txns, max_ins=max_ins)
        assert dl.max() <= max_ins and il.max() <= max_ins
        docs, lens = replay_batch(
            jnp.asarray(pos[None]), jnp.asarray(dl[None]),
            jnp.asarray(il[None]), jnp.asarray(chars[None]), cap=32)
        out = docs_to_strings(np.asarray(docs), np.asarray(lens))
        assert out[0] == expected, max_ins


def test_replay_out_of_contract_ops_poison_length():
    """Ops violating the dlen/ilen <= max_ins contract must not silently
    produce wrong text: the length comes back -1."""
    pos = np.zeros((1, 2), np.int32)
    il = np.asarray([[4, 0]], np.int32)
    dl = np.asarray([[0, 9]], np.int32)   # out of contract (max_ins = 4)
    chars = np.zeros((1, 2, 4), np.int32)
    chars[0, 0] = [104, 105, 33, 33]
    _docs, lens = replay_batch(jnp.asarray(pos), jnp.asarray(dl),
                               jnp.asarray(il), jnp.asarray(chars), cap=16)
    assert int(np.asarray(lens)[0]) == -1


def test_materialize_pallas_parity():
    """Pallas run-expansion (interpret mode) vs materialize_jax on random
    run tables and on a real corpus's device-doc tables."""
    import jax.numpy as jnp
    import numpy as np
    import random
    from diamond_types_tpu.tpu.linearize import materialize_jax
    from diamond_types_tpu.tpu.pallas_kernels import materialize_pallas

    rng = random.Random(77)
    for trial in range(12):
        n = rng.randint(1, 50)
        vis = np.array([rng.choice([0, 0, 1, 2, 5]) for _ in range(n)],
                       dtype=np.int32)
        arena = np.arange(1000, dtype=np.int32) + 100
        off = np.array([rng.randrange(900) for _ in range(n)],
                       dtype=np.int32)
        perm = np.random.RandomState(trial).permutation(n).astype(np.int32)
        cap = int(max(8, 1 << int(vis.sum()).bit_length()))
        t1, n1 = materialize_jax(jnp.asarray(perm), jnp.asarray(vis),
                                 jnp.asarray(off), jnp.asarray(arena),
                                 cap=cap)
        t2, n2 = materialize_pallas(jnp.asarray(perm), jnp.asarray(vis),
                                    jnp.asarray(off), jnp.asarray(arena),
                                    cap=cap, interpret=True)
        assert int(n1) == int(n2)
        assert np.array_equal(np.asarray(t1)[:int(n1)],
                              np.asarray(t2)[:int(n2)]), f"trial {trial}"


def test_materialize_pallas_corpus():
    """Byte parity through the full merge-kernel path with the Pallas
    materialize stage swapped in (friendsforever corpus)."""
    import numpy as np
    import jax.numpy as jnp
    from conftest import reference_path
    from diamond_types_tpu.encoding.decode import load_oplog
    from diamond_types_tpu.tpu.merge_kernel import prepare_doc
    from diamond_types_tpu.tpu.linearize import fugue_linearize_jax
    from diamond_types_tpu.tpu.pallas_kernels import materialize_pallas

    with open(reference_path("benchmark_data", "friendsforever.dt"),
              "rb") as f:
        ol = load_oplog(f.read())
    doc = prepare_doc(ol)
    n = doc.parent.shape[0]
    perm = fugue_linearize_jax(
        jnp.asarray(np.where(doc.parent == n, n, doc.parent)),
        jnp.asarray(doc.side.astype(np.int32)),
        jnp.asarray(doc.key_pos), jnp.asarray(doc.key_agent),
        jnp.asarray(doc.key_seq))
    cap = 1 << int(doc.total_len).bit_length()
    text, total = materialize_pallas(
        perm, jnp.asarray(doc.vis_len), jnp.asarray(doc.char_off),
        jnp.asarray(doc.chars), cap=cap, interpret=True)
    got = np.asarray(text)[:int(total)].astype(np.int32).tobytes() \
        .decode("utf-32-le")
    assert got == ol.checkout_tip().snapshot()


def test_pallas_kernels_lower_for_tpu():
    """Offline Mosaic lowering of every Pallas kernel (no TPU needed:
    .lower(lowering_platforms=('tpu',)) runs the full Mosaic kernel
    lowering pass on any backend).

    Regression for the 2026-07-31 on-chip failures: interpret-mode tests
    passed kernels the Mosaic backend cannot compile (first mismatched
    gather shapes, then dynamic_gather spanning multiple vregs — the
    backend limit that forced the gather-free redesign). The real
    tpu_merge_git_makefile_pallas bench died at compile time three
    rounds in a row while CI stayed green; this test makes the lowering
    contract a host-side assertion. (The backend's vreg-level layout
    checks run server-side only, so this cannot catch everything — the
    kernels are designed against the documented legal-op set instead:
    scalar-controlled rolls, dynamic-offset block copies, no gathers.)"""
    import unittest.mock as mock

    import jax
    import jax.numpy as jnp
    from diamond_types_tpu.tpu import pallas_kernels as pk
    from diamond_types_tpu.tpu.merge_kernel import _checkout_kernel

    perm = jnp.arange(200, dtype=jnp.int32)
    vis = jnp.ones(200, dtype=jnp.int32)
    aoff = jnp.arange(200, dtype=jnp.int32)
    arena = jnp.zeros(70000, dtype=jnp.int32)

    def mat(perm, vis, aoff, arena):
        return pk.materialize_pallas(perm, vis, aoff, arena, cap=300,
                                     interpret=False)

    # materialize_pallas consults jax.default_backend() to pick the
    # interpret fallback; pretend to be on TPU so the real kernel lowers.
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        jax.jit(mat).trace(perm, vis, aoff, arena).lower(
            lowering_platforms=("tpu",))

    pos = jnp.zeros((8,), jnp.int32)
    dl = jnp.zeros((8,), jnp.int32)
    il = jnp.ones((8,), jnp.int32)
    ch = jnp.zeros((8, 16), jnp.int32)
    doc = jnp.zeros((8, 256), jnp.int32)
    dlen = jnp.zeros((8,), jnp.int32)
    jax.jit(lambda *a: pk.apply_op_block(*a, interpret=False)).trace(
        pos, dl, il, ch, doc, dlen).lower(lowering_platforms=("tpu",))

    # The production DT_TPU_PALLAS=1 entry point: the batch-unrolled
    # checkout (fugue linearize composed with the pallas materialize) —
    # the exact function bench_device_merge(pallas=True) compiles.
    B, n = 3, 64
    cols = (jnp.full((B, n), n, jnp.int32),          # parent (roots)
            jnp.zeros((B, n), jnp.int8),             # side
            jnp.zeros((B, n), jnp.int32),            # key_pos
            jnp.zeros((B, n), jnp.int32),            # key_agent
            jnp.arange(n, dtype=jnp.int32)[None].repeat(B, 0),  # key_seq
            jnp.ones((B, n), jnp.int32),             # vis_len
            jnp.arange(n, dtype=jnp.int32)[None].repeat(B, 0),  # char_off
            jnp.full((B, n), 97, jnp.int32))         # chars

    import functools

    def run_all(*cols):
        single = functools.partial(_checkout_kernel, cap=128, pallas=True)
        outs = [single(*(c[i] for c in cols)) for i in range(B)]
        return (jnp.stack([t for t, _ in outs]),
                jnp.stack([x for _, x in outs]))

    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        jax.jit(run_all).trace(*cols).lower(lowering_platforms=("tpu",))
