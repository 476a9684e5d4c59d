"""The process's first touch of the JAX backend, in one place.

Every device path the serve tier can reach (`serve/bank.py` session
builds and warm-up, `serve/scheduler.py` shard placement,
`parallel/mesh.py` serve meshes, `chip_smoke.py`) goes through
`first_touch()` before it asks JAX for a device. There, once per
process and under one lock (backend bootstrap is process-global and not
thread-safe):

  1. `jax.devices()` runs,
  2. the persistent compile cache is placed (`configure_compile_cache`),
  3. `platform / device_kind / device count` is logged once to stderr,
  4. the call raises `NoAccelerator` unless the platform is `tpu`, or
     the environment named `cpu` itself (`JAX_PLATFORMS=cpu`, as the
     tests' conftest and the sandbox do). libtpu is installed on
     machines without a chip too, and JAX then settles on the CPU with
     a warning; a process that asked for a device engine must not carry
     on there under the device engine's name.

Importing this module does not import jax.
"""

from __future__ import annotations

import os
import sys
import threading
from typing import Dict, List, Optional

from ..analysis.witness import make_lock as _make_lock

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
# fixed path: the directory is part of the cache key's lookup, so a
# temp name, pid or time in it would never hit
DEFAULT_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")
CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_first_touch_lock = _make_lock("first_touch", "leaf")
_device_info: Optional[Dict] = None


class NoAccelerator(RuntimeError):
    """JAX found no TPU and the environment did not name `cpu`."""


def configure_compile_cache(platform: str) -> Optional[str]:
    """Place JAX's persistent compile cache for a process on `platform`.
    Must run before the first compilation; `first_touch` calls it.

    Where `JAX_COMPILATION_CACHE_DIR` is set the environment owns the
    directory and none is set in code. Otherwise a TPU process caches
    at `<checkout>/.jax_cache`; a CPU process (tests, the sandbox
    pre-flight) gets no cache it was not given — nothing there is worth
    keeping, and XLA:CPU logs an error line per reloaded entry. On the
    TPU the write threshold (1 s of compile time by default) is lowered
    so the small replay kernels are kept, unless the environment names
    one. Returns the directory in use, or None."""
    import jax
    if platform == "tpu":
        if not os.environ.get(CACHE_DIR_ENV):
            jax.config.update("jax_compilation_cache_dir",
                              DEFAULT_CACHE_DIR)
        if not os.environ.get(
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
            jax.config.update(
                "jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir


def cpu_named() -> bool:
    """Did the environment ask for the CPU by name? `jax_platforms` is
    initialised from `JAX_PLATFORMS`; the only code that writes it is
    tests/conftest.py and the CPU dry run."""
    import jax
    named = (jax.config.jax_platforms or "").lower().split(",")
    return "cpu" in named


def first_touch() -> Dict:
    """Initialise the backend (once) and return
    {"platform", "device_kind", "count", "cache_dir"} as JAX reports
    them. Raises `NoAccelerator` per the module docstring."""
    global _device_info
    info = _device_info
    if info is not None:
        return info
    with _first_touch_lock:
        if _device_info is not None:
            return _device_info
        import jax
        devs = jax.devices()    # initialises the backend, compiles nothing
        info = {"platform": devs[0].platform,
                "device_kind": devs[0].device_kind,
                "count": len(devs),
                "cache_dir": configure_compile_cache(devs[0].platform)}
        if info["platform"] != "tpu" and not (
                info["platform"] == "cpu" and cpu_named()):
            raise NoAccelerator(
                f"JAX found no TPU (platform={info['platform']!r}, "
                f"{info['count']} x {info['device_kind']!r}) and the "
                "environment did not name cpu; set JAX_PLATFORMS=cpu to "
                "run the device engine on the CPU on purpose")
        COMPILE_STATS.install()
        print(f"[dt] device: platform={info['platform']} "
              f"device_kind={info['device_kind']!r} "
              f"count={info['count']} "
              f"compile_cache={info['cache_dir']}",
              file=sys.stderr, flush=True)
        _device_info = info
        return info


def devices() -> List:
    """`jax.devices()` behind the first-touch guard."""
    first_touch()
    import jax
    return jax.devices()


def pallas_interpret() -> bool:
    """Should a Pallas kernel run interpreted? Only off the TPU — and
    with `first_touch`'s guard a served process is off the TPU only when
    `cpu` was asked for by name (tests, the sandbox pre-flight). The one
    place that maps backend to interpret mode."""
    import jax
    return jax.default_backend() != "tpu"


class CompileStats:
    """Counts what JAX compiles, from its own monitoring events:
    `compiles` / `compile_s` are backend compilations (a persistent-
    cache retrieval counts as one, at its retrieval time), `trace_s`
    the tracing + lowering in front of them, and `cache_hits` /
    `cache_misses` the persistent cache's own events (a miss is counted
    when the entry is written). Set-up accounting, not a speed."""

    _DURATIONS = {
        "/jax/core/compile/backend_compile_duration": "compile_s",
        "/jax/core/compile/jaxpr_trace_duration": "trace_s",
        "/jax/core/compile/jaxpr_to_mlir_module_duration": "trace_s",
    }
    _EVENTS = {
        "/jax/compilation_cache/cache_hits": "cache_hits",
        "/jax/compilation_cache/cache_misses": "cache_misses",
        "/jax/compilation_cache/compile_requests_use_cache":
            "cache_requests",
    }

    def __init__(self) -> None:
        self._lock = threading.Lock()
        self._installed = False
        self._c = {"compiles": 0, "compile_s": 0.0, "trace_s": 0.0,
                   "cache_hits": 0, "cache_misses": 0,
                   "cache_requests": 0}

    def install(self) -> None:
        if self._installed:
            return
        self._installed = True
        import jax.monitoring as mon
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, duration: float, **_kw) -> None:
        key = self._DURATIONS.get(event)
        if key is None:
            return
        with self._lock:
            self._c[key] += duration
            if key == "compile_s":
                self._c["compiles"] += 1

    def _on_event(self, event: str, **_kw) -> None:
        key = self._EVENTS.get(event)
        if key is not None:
            with self._lock:
                self._c[key] += 1

    def snapshot(self) -> Dict:
        with self._lock:
            return dict(self._c)

    @staticmethod
    def delta(now: Dict, base: Dict) -> Dict:
        return {k: round(now[k] - base[k], 3) if isinstance(now[k], float)
                else now[k] - base[k] for k in now}


COMPILE_STATS = CompileStats()
