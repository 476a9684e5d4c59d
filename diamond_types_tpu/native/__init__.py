"""Native (C++) host core bindings via ctypes.

The hot merge path (graph queries + spanning-tree walk + treap tracker +
transform pipeline) is implemented in native/dt_core.cpp, mirroring how the
reference implements its host tier in Rust. The binaries are built on first
import (native/build.py; not in git). Library callers fall back to the pure
implementation in diamond_types_tpu.listmerge when the build or load fails;
the served path calls `require_native()` so that fallback cannot pass unseen.
"""

from .core import (NativeContext, merge_native, native_available,  # noqa: F401
                   transform_native)


def native_ctx_or_none(oplog):
    """The oplog's native context, or None when the native engine is
    disabled (DT_TPU_NO_NATIVE) or the library is unavailable — the one
    gate for every native fast path that needs a per-oplog context
    (composer, encoder, merge, conflict counting). The fresh-load decoder
    gates separately (no oplog exists yet at decode time)."""
    import os
    if os.environ.get("DT_TPU_NO_NATIVE"):
        return None
    if not native_available():
        return None
    from .core import get_native_ctx
    return get_native_ctx(oplog)


class NativeUnavailable(RuntimeError):
    """The native host core failed to build or load."""


def require_native() -> bool:
    """True when the native library and the ingest extension are loaded;
    False when the pure-Python engine was asked for by name
    (DT_TPU_NO_NATIVE=1). A build or load failure is neither: it raises
    `NativeUnavailable` with the compiler's or loader's words. Called at
    `serve()` start-up and by chip_smoke.py."""
    import os
    if os.environ.get("DT_TPU_NO_NATIVE"):
        return False
    from . import core, ingest
    if core._load() is None:
        raise NativeUnavailable(f"libdt_core: {core._load_error}")
    if ingest._load_ext() is None:
        raise NativeUnavailable(f"_dtingest: {ingest._ext_error}")
    return True
