"""Device-session endurance soak — hours of realtime merge-per-edit
traffic against the chip this process owns, parity-checked against the
host engine on every sync.

The device benches measure per-call latency over seconds; this harness
measures something they cannot: sustained runtime stability. It drives
a `DeviceZoneSession` (tpu/zone_session.py) with the same 2-agent
continuation shape as the session bench — each agent keeps typing from
its own head — and asserts `sess.text() == oplog.checkout_tip()
.snapshot()` after EVERY sync, so the device state, the sliced-resync
path (capacity growth naturally forces full rebuilds as the document
grows), and the micro-tape continuation are all parity-gated for the
whole run. A device worker crash is caught, logged, and recovered from
by rebuilding the session; a parity MISMATCH is logged and stops the
run (that is a correctness bug, not an environment event).

One process owns the chip for the whole soak: start nothing else that
needs it meanwhile. The device is reached through `runtime.first_touch`,
so without a TPU the soak refuses to start unless JAX_PLATFORMS=cpu
names the CPU.

Usage:
  python -m diamond_types_tpu.tools.device_soak \
      --corpus path/to/doc.dt --hours 3 --log soak.jsonl
`--corpus` is a .dt file (absolute, or relative to the reference
checkout's benchmark_data, which this repo does not carry).
Stop early: touch .stop_device_soak in the repo root.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import sys
import time
import traceback

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
_STOP = os.path.join(_REPO_ROOT, ".stop_device_soak")
_BENCH_DATA = "/root/reference/benchmark_data"


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--corpus", default="friendsforever.dt")
    p.add_argument("--hours", type=float, default=3.0)
    p.add_argument("--seed", type=int, default=7)
    p.add_argument("--batch-max", type=int, default=8,
                   help="max edits folded per sync")
    p.add_argument("--max-recovery-failures", type=int, default=5,
                   help="bail out after this many CONSECUTIVE failed "
                   "session rebuilds (the runtime is gone, not flaky)")
    p.add_argument("--log", default=None)
    args = p.parse_args(argv)

    out = open(args.log, "a") if args.log else sys.stdout

    def emit(obj):
        obj["ts"] = round(time.time(), 1)
        out.write(json.dumps(obj, ensure_ascii=False) + "\n")
        out.flush()

    from ..encoding.decode import load_oplog
    from ..tpu.runtime import first_touch
    from ..tpu.zone_session import DeviceZoneSession

    device = first_touch()

    with open(os.path.join(_BENCH_DATA, args.corpus), "rb") as f:
        ol = load_oplog(f.read())
    emit({"event": "soak_start", "corpus": args.corpus,
          "backend": device["platform"],
          "device_kind": device["device_kind"], "hours": args.hours,
          "n_ops_start": len(ol)})

    rng = random.Random(args.seed)
    t_build0 = time.time()
    sess = DeviceZoneSession(ol)
    sess.touch()
    emit({"event": "session_built",
          "build_s": round(time.time() - t_build0, 1)})

    # The 2-agent continuation shape needs two agents that each OWN at
    # least one op: heads come from _agent_last_lv, and a None head
    # (agent registered but opless, or a single-agent linear corpus)
    # would crash the first one_edit with a useless traceback hours
    # into an unattended run. Validate up front; a missing SECOND
    # agent is repairable by seeding one op at the tip.
    agents = [a for a in range(len(ol.cg.agent_assignment.agent_names))
              if sess._agent_last_lv(a) is not None][:2]
    if not agents:
        emit({"event": "soak_abort", "fatal": True,
              "why": f"corpus {args.corpus} has no agent with any ops; "
              "cannot derive an editing head (pick a non-empty corpus)"})
        return 1
    heads = {}
    if len(agents) == 1:
        a2 = ol.get_or_create_agent_id("device-soak-2")
        heads[a2] = [ol.add_insert_at(a2, list(ol.version), 0, "q")]
        agents.append(a2)
        emit({"event": "seeded_second_agent", "agent": "device-soak-2",
              "why": "corpus has a single editing agent; the soak's "
              "continuation shape needs two concurrent heads"})
    for a in agents:
        heads.setdefault(a, [sess._agent_last_lv(a)])
    lens = {a: len(ol.checkout(heads[a]).snapshot()) for a in agents}

    def one_edit(a):
        # inserts only: deletes at random positions are covered by the
        # CI fuzz; growth is the POINT here (it forces capacity resyncs)
        pos = rng.randrange(max(lens[a], 1))
        n = rng.randint(1, 4)
        heads[a] = [ol.add_insert_at(a, heads[a], pos, "q" * n)]
        lens[a] += n

    deadline = time.time() + args.hours * 3600
    syncs = edits = crashes = 0
    recovery_failures = 0
    recovering = False
    resyncs0 = sess.resyncs
    t_report = time.time()
    while time.time() < deadline and not os.path.exists(_STOP):
        if recovering:
            # Rebuild WITHOUT appending new edits: every failed rebuild
            # would otherwise grow the oplog, making each retry strictly
            # harder than the last (and the backlog meaningless). Bail
            # once the failures are consecutive enough to mean "the
            # runtime is gone", not "the runtime blipped".
            try:
                sess = DeviceZoneSession(ol)
                sess.touch()
                got = sess.text()
            except Exception:
                recovery_failures += 1
                emit({"event": "recovery_failed",
                      "consecutive": recovery_failures,
                      "max": args.max_recovery_failures,
                      "error": traceback.format_exc(limit=1)
                      .strip().splitlines()[-1][:200]})
                if recovery_failures >= args.max_recovery_failures:
                    emit({"event": "soak_abort", "fatal": True,
                          "why": f"{recovery_failures} consecutive "
                          "session rebuilds failed; giving up",
                          "syncs": syncs, "edits": edits,
                          "crashes": crashes})
                    return 2
                time.sleep(120)
                continue
            recovering = False
            recovery_failures = 0
            emit({"event": "recovered", "syncs": syncs, "edits": edits})
        else:
            k = rng.randint(1, args.batch_max)
            for i in range(k):
                one_edit(agents[(edits + i) % 2])
            edits += k
            try:
                sess.sync()
                got = sess.text()
            except Exception:
                crashes += 1
                emit({"event": "device_crash", "crashes": crashes,
                      "error": traceback.format_exc(limit=1)
                      .strip().splitlines()[-1][:200]})
                # recover: rebuild the whole session (exercises the
                # sliced resync on the grown oplog) after a settle; the
                # recovery loop above owns the retries
                time.sleep(30)
                recovering = True
                continue
        expected = ol.checkout_tip().snapshot()
        if got != expected:
            emit({"event": "PARITY_MISMATCH", "syncs": syncs,
                  "edits": edits, "fatal": True})
            return 1
        syncs += 1
        if time.time() - t_report > 120:
            emit({"event": "progress", "syncs": syncs, "edits": edits,
                  "resyncs": sess.resyncs - resyncs0, "crashes": crashes,
                  "doc_chars": len(expected), "n_ops": len(ol),
                  "elapsed_s": round(time.time() - (deadline -
                                                    args.hours * 3600))})
            t_report = time.time()
    emit({"event": "soak_end", "syncs": syncs, "edits": edits,
          "resyncs": sess.resyncs - resyncs0, "crashes": crashes,
          "parity": "all syncs byte-identical", "n_ops_end": len(ol)})
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
