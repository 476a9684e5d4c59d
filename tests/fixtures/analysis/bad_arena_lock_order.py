"""Seeded dt-lint fixture: window-arena guard lock-order violation.

Acquires the oplog guard (30) while already holding the window arena's
recycle-table guard (`_arena_lock`, device, 40) — backwards against
the canonical order: the arena's acquire/adopt bracket the mesh
dispatch OUTSIDE the oplog guard by design, so staging code releases
the oplog rung before the arena guard, never re-enters under it.
Never imported; parsed by the lint engine only.
"""


class FixtureWindowArena:
    def backwards(self, sessions):
        with self._arena_lock:
            with self.store.lock:
                return [self._recycle(s) for s in sessions]
