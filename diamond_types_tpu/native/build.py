"""Build the native host core: g++ -O3 -shared -fPIC native/dt_core.cpp.

The binaries are not in git: a checkout builds what it runs, on first
import. They are compiled with `-march=native`, so a binary is only
good on a CPU with the flags it was built for. The rebuild gate is
therefore a stamp file beside each binary holding a digest of the
sources, the compiler command and the host CPU's feature flags; a
binary that arrived from another machine (a copied tree) or predates a
source edit fails the comparison and is rebuilt, never trusted.
"""

from __future__ import annotations

import hashlib
import os
import platform
import subprocess
import sys
from typing import List

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
SRC = os.path.join(REPO, "native", "dt_core.cpp")
SRC_DECODE = os.path.join(REPO, "native", "dt_decode.cpp")
OUT = os.path.join(REPO, "native", "libdt_core.so")
SRC_INGEST = os.path.join(REPO, "native", "dt_ingest.cpp")


class NativeBuildError(RuntimeError):
    """The native sources are missing or the compiler refused them."""


def _ingest_out() -> str:
    # ABI-tagged filename (e.g. _dtingest.cpython-312-x86_64-linux-gnu.so):
    # unlike the ctypes-driven libdt_core.so this is a real CPython
    # extension, and loading one built for another interpreter is UB
    import sysconfig
    return os.path.join(REPO, "native",
                        "_dtingest" + sysconfig.get_config_var("EXT_SUFFIX"))


def _host_cpu_flags() -> str:
    """The feature flags `-march=native` compiles for."""
    try:
        with open("/proc/cpuinfo", encoding="utf8") as f:
            for line in f:
                if line.startswith(("flags", "Features")):
                    return line.split(":", 1)[1].strip()
    except OSError:
        pass
    return f"{platform.machine()} {platform.processor()}"


def _stamp(srcs: List[str], cmd: List[str]) -> str:
    h = hashlib.sha256()
    for s in srcs:
        with open(s, "rb") as f:
            h.update(f.read())
    h.update("\0".join(cmd).encode("utf8"))
    h.update(_host_cpu_flags().encode("utf8"))
    return h.hexdigest()


def _build(srcs: List[str], cmd: List[str], out: str, force: bool) -> str:
    """Run `cmd + [-o out]` unless `out`'s stamp already matches. The
    compiler writes a temp name that is renamed into place, and the
    stamp is written last, so a concurrent or interrupted build never
    leaves a half-written binary under a valid stamp."""
    stamp = _stamp(srcs, cmd)
    stamp_path = out + ".stamp"
    if not force and os.path.exists(out):
        try:
            with open(stamp_path, encoding="utf8") as f:
                if f.read().strip() == stamp:
                    return out
        except OSError:
            pass
    tmp = f"{out}.{os.getpid()}.tmp"
    try:
        subprocess.run(cmd + ["-o", tmp], check=True, capture_output=True,
                       text=True)
        os.replace(tmp, out)
    except (subprocess.CalledProcessError, OSError) as e:
        detail = (getattr(e, "stderr", "") or "")[:2000]
        raise NativeBuildError(f"{os.path.basename(out)}: {e}\n{detail}"
                               .rstrip()) from e
    finally:
        if os.path.exists(tmp):
            os.unlink(tmp)
    with open(f"{stamp_path}.{os.getpid()}.tmp", "w", encoding="utf8") as f:
        f.write(stamp + "\n")
    os.replace(f.name, stamp_path)
    return out


def build(force: bool = False) -> str:
    if not os.path.exists(SRC):
        raise NativeBuildError(f"missing source {SRC}")
    srcs = [SRC] + ([SRC_DECODE] if os.path.exists(SRC_DECODE) else [])
    # -fno-semantic-interposition: lets the compiler inline across
    # functions inside the DSO despite -fPIC (ELF interposition rules
    # otherwise force calls through the PLT); ~14% on the git-makefile
    # merge in interleaved A/B runs. (-flto HURTS the shared build —
    # measured 20% slower — even though it helps the static bench binary.)
    cmd = ["g++", "-O3", "-march=native", "-fno-semantic-interposition",
           "-std=c++17", "-shared", "-fPIC", "-DNDEBUG", *srcs]
    return _build(srcs, cmd, OUT, force)


def build_ingest(force: bool = False) -> str:
    """Build the local-ingest CPython extension (native/dt_ingest.cpp).

    A real extension module (not ctypes) because the per-call overhead
    IS the hot path being fixed — see dt_ingest.cpp's header comment."""
    if not os.path.exists(SRC_INGEST):
        raise NativeBuildError(f"missing source {SRC_INGEST}")
    import sysconfig
    inc = sysconfig.get_paths()["include"]
    cmd = ["g++", "-O3", "-march=native", "-std=c++17", "-shared", "-fPIC",
           "-DNDEBUG", f"-I{inc}", SRC_INGEST]
    return _build([SRC_INGEST], cmd, _ingest_out(), force)


if __name__ == "__main__":
    # a broken build must fail loudly: the ingest tests skip when the
    # extension is unavailable, so a silent exit-0 would leave the
    # parity suite green with zero coverage
    rc = 0
    for fn in (build, build_ingest):
        try:
            print(fn(force="--force" in sys.argv))
        except NativeBuildError as e:
            print(f"BUILD FAILED: {e}")
            rc = 1
    sys.exit(rc)
