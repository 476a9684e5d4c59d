"""Share of the documents the window's autosave passes encoded that
were encoded from the oplog's native mirror OUTSIDE `DocStore.lock`
(`encode_mirror`) and not by the writer under it: 100 * docs_unlocked /
(docs_unlocked + docs_locked), the `autosave.pass` row's own counts.
None on a program without the counters. The four-chip cell's copy of
`store.autosave_unlocked_share.sat` (a reader is found by its file's
name)."""
from bench import phases


def read(ctx):
    out = phases.delta(ctx, "autosave.pass", "counts.docs_unlocked")
    under = phases.delta(ctx, "autosave.pass", "counts.docs_locked")
    if out is None or under is None:
        return None
    return phases.ratio(out, out + under, 100.0)
