#!/usr/bin/env python3
"""Controls: the harness with the timed path broken underneath.

Each control breaks one guarantee that the configurations state
(`bench/configs/*.json`), where the program produces the answer, and the
run must then print `"correct": false`:

  lose_final_flush   nothing reaches the disk: an edit acknowledged
                     before the clean shutdown does not read back from
                     a second server on the same data directory
  withhold_edit      one edit in seven is acknowledged and not applied:
                     the HTTP body no longer equals the reference
  replay_unchanged   one device replay in five returns its state
                     unchanged while the session's bookkeeping moves
                     on: the device session no longer holds the bytes
                     (the program's length fence notices and falls
                     back to the host: `host_fallbacks` is not 0)
  replay_wrong_char  every replay leaves the first character wrong and
                     the lengths right: only the comparison of the
                     device session with the reference can see it

    python3 bench/tests/controls.py --break <name> --workload <cell> \\
        --seed <n> --seconds <s> [--tiny]

runs one and prints `{"break", "workload", "seed", "correct", ...}` as
its last line; the exit code is 0 when `correct` came out false. On the
chip it runs at the cell's own size; `bench/tests/test_bench.py` runs
each at the CPU rehearsal's size.
"""

from __future__ import annotations

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


class Broken:
    def server_started(self, httpd) -> None:
        pass

    def before_shutdown(self, httpd) -> None:
        pass


class LoseFinalFlush(Broken):
    def server_started(self, httpd) -> None:
        httpd.store.flush = lambda force=False: None


class WithholdEdit(Broken):
    every = 7

    def server_started(self, httpd) -> None:
        handler = httpd.RequestHandlerClass
        inner = handler._do_post
        seen = [0]
        every = self.every

        def _do_post(self):
            if self.path.endswith("/edit"):
                seen[0] += 1
                if seen[0] % every == 0:
                    n = int(self.headers.get("Content-Length", 0))
                    req = json.loads(self.rfile.read(n))
                    return self._send(200, json.dumps(
                        {"version": req.get("version") or []}).encode())
            return inner(self)

        handler._do_post = _do_post


class ReplayUnchanged(Broken):
    every = 5

    def server_started(self, httpd) -> None:
        from diamond_types_tpu.tpu import flush_fuse
        self._cls = flush_fuse.FusedDocSession
        self._commit = commit = self._cls.commit
        seen = [0]
        every = self.every

        def broken_commit(sess, docs, lens, plan):
            seen[0] += 1
            if seen[0] % every == 0:
                docs, lens = sess.docs, sess.lens
            return commit(sess, docs, lens, plan)

        self._cls.commit = broken_commit

    def before_shutdown(self, httpd) -> None:
        self._cls.commit = self._commit


class ReplayWrongChar(Broken):
    """A replay that leaves one character wrong and every length right:
    the program's own length fence cannot see it, only the comparison
    of the device session with the reference can."""

    def server_started(self, httpd) -> None:
        from diamond_types_tpu.tpu import flush_fuse
        self._cls = flush_fuse.FusedDocSession
        self._commit = commit = self._cls.commit

        def broken_commit(sess, docs, lens, plan):
            return commit(sess, docs.at[0].add(1), lens, plan)

        self._cls.commit = broken_commit

    def before_shutdown(self, httpd) -> None:
        self._cls.commit = self._commit


BREAKS = {"lose_final_flush": LoseFinalFlush,
          "withhold_edit": WithholdEdit,
          "replay_unchanged": ReplayUnchanged,
          "replay_wrong_char": ReplayWrongChar}


def main() -> int:
    i = sys.argv.index("--break")
    name, argv = sys.argv[i + 1], sys.argv[1:i] + sys.argv[i + 2:]
    from bench import run
    rc, result = run.run_cli(argv, broken=BREAKS[name]())
    if result is None:
        print(json.dumps({"break": name, "error": "no result line"}))
        return 2
    args = dict(zip(argv[::2], argv[1::2]))
    print(json.dumps(result), flush=True)
    print(json.dumps({"break": name, "workload": args.get("--workload"),
                      "seed": args.get("--seed"),
                      "correct": result["correct"],
                      "failed": result["failed"],
                      "attempted": result["attempted"]}), flush=True)
    return 0 if result["correct"] is False else 1


if __name__ == "__main__":
    sys.exit(main())
