"""Bring-up contracts (PR 21): a served process that owns its chip.

  * the first JAX touch of every served device path is one guarded
    site: a TPU, or the CPU asked for by name — never a CPU by accident;
  * the compile cache is placed from outside (JAX_COMPILATION_CACHE_DIR)
    or at one fixed path, never in code over the environment;
  * no program code writes the platform;
  * a warm-up compile failure surfaces, a pump-loop exception is
    counted, the native core's absence is an error on the served path;
  * the native binaries are rebuilt when their stamp does not match;
  * the Pallas kernels the serve ladder reaches lower for the TPU;
  * `serve(engine="device")` and `chip_smoke.py --tiny` run end to end
    on the CPU, and the smoke refuses to pass without a TPU.

Runs on the CPU the conftest names; nothing here measures a speed.
"""

import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PKG = os.path.join(REPO, "diamond_types_tpu")


def _program_sources():
    out = [os.path.join(REPO, "chip_smoke.py")]
    for root, _dirs, files in os.walk(PKG):
        out += [os.path.join(root, f) for f in files if f.endswith(".py")]
    return out


def _python(argv, env=None, drop=(), cwd=REPO, timeout=600):
    """A fresh interpreter with `drop` removed from and `env` added to
    the conftest's environment."""
    e = {k: v for k, v in os.environ.items() if k not in drop}
    e.update(env or {})
    return subprocess.run([sys.executable] + argv, env=e, cwd=cwd,
                          capture_output=True, text=True, timeout=timeout)


def _run(code, **kw):
    return _python(["-c", code], **kw)


def _smoke(args, **kw):
    return _python([os.path.join(REPO, "chip_smoke.py")] + args, **kw)


# ---- first touch -------------------------------------------------------------

def test_first_touch_reports_the_named_cpu_once():
    from diamond_types_tpu.tpu import runtime
    info = runtime.first_touch()
    assert info["platform"] == "cpu" and info["count"] >= 1
    assert set(info) == {"platform", "device_kind", "count", "cache_dir"}
    assert runtime.first_touch() is info            # once per process
    assert len(runtime.devices()) == info["count"]


def test_first_touch_refuses_a_cpu_nobody_asked_for(monkeypatch):
    """libtpu without a chip leaves JAX on the CPU with a warning; the
    guard turns that into an error unless cpu was named."""
    from diamond_types_tpu.tpu import runtime
    monkeypatch.setattr(runtime, "_device_info", None)
    monkeypatch.setattr(runtime, "cpu_named", lambda: False)
    with pytest.raises(runtime.NoAccelerator, match="JAX_PLATFORMS=cpu"):
        runtime.first_touch()
    assert runtime._device_info is None             # nothing cached
    with pytest.raises(runtime.NoAccelerator):      # a device scheduler
        from diamond_types_tpu.serve import MergeScheduler
        MergeScheduler(1, resolve=lambda d: None, engine="device")


def test_first_touch_without_the_variable_fails_in_a_fresh_process():
    r = _run("from diamond_types_tpu.tpu.runtime import first_touch\n"
             "print(first_touch())", drop=("JAX_PLATFORMS",))
    assert r.returncode != 0
    assert "NoAccelerator" in r.stderr


def test_first_touch_is_the_single_site_on_the_served_path():
    """`jax.devices()` — the backend's initialisation — is called in one
    program file; every served device path goes through it."""
    callers = [os.path.relpath(p, REPO) for p in _program_sources()
               if re.search(r"\bjax\.devices\(", open(p).read())]
    served = [c for c in callers if c.startswith((
        "diamond_types_tpu/serve/", "diamond_types_tpu/parallel/",
        "diamond_types_tpu/tools/server.py", "chip_smoke.py",
        "diamond_types_tpu/tpu/flush_fuse.py",
        "diamond_types_tpu/tpu/runtime.py"))]
    assert served == ["diamond_types_tpu/tpu/runtime.py"]


def test_no_program_code_writes_the_platform():
    """The platform is the environment's: only tests/conftest.py and the
    CPU dry run (__graft_entry__.dryrun_multichip) name it in code."""
    pat = re.compile(
        r"environ\[\s*[\"'](JAX_PLATFORMS|XLA_FLAGS)[\"']\s*\]\s*=|"
        r"environ\.setdefault\(\s*[\"'](JAX_PLATFORMS|XLA_FLAGS)|"
        r"putenv\(\s*[\"'](JAX_PLATFORMS|XLA_FLAGS)|"
        r"config\.update\(\s*[\"']jax_platforms?[\"']")
    offenders = [os.path.relpath(p, REPO) for p in _program_sources()
                 if pat.search(open(p).read())]
    assert offenders == []


# ---- compile cache -------------------------------------------------------------

_CACHE_PROBE = """
import os, json, jax, jax.numpy as jnp
from diamond_types_tpu.tpu import runtime
before = sorted(os.listdir(runtime.DEFAULT_CACHE_DIR)) \\
    if os.path.isdir(runtime.DEFAULT_CACHE_DIR) else None
got = runtime.configure_compile_cache("tpu")
jax.block_until_ready(jax.jit(lambda x: x * 2 + 1)(jnp.arange(8)))
after = sorted(os.listdir(runtime.DEFAULT_CACHE_DIR)) \\
    if os.path.isdir(runtime.DEFAULT_CACHE_DIR) else None
print(json.dumps({"dir": got, "config": jax.config.jax_compilation_cache_dir,
                  "untouched": before == after,
                  "cpu": runtime.configure_compile_cache("cpu")}))
"""


def test_cache_dir_from_the_environment_is_left_alone(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: nothing is set in code and nothing
    is written under <checkout>/.jax_cache."""
    r = _run(_CACHE_PROBE,
             env={"JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")})
    assert r.returncode == 0, r.stderr
    out = json.loads(r.stdout.strip().splitlines()[-1])
    assert out["dir"] == out["config"] == str(tmp_path / "cc")
    assert out["untouched"]
    assert os.listdir(tmp_path / "cc")      # the env's dir took the entry


def test_cache_dir_default_is_one_fixed_path():
    """Unset: a TPU process caches at <checkout>/.jax_cache — the same
    path in every process, no temp name, pid or time in it — and a CPU
    process is given none."""
    from diamond_types_tpu.tpu import runtime
    assert runtime.DEFAULT_CACHE_DIR == os.path.join(REPO, ".jax_cache")
    code = ("from diamond_types_tpu.tpu import runtime\n"
            "print(runtime.configure_compile_cache('cpu'))\n"
            "print(runtime.configure_compile_cache('tpu'))")
    runs = [_run(code, drop=("JAX_COMPILATION_CACHE_DIR",))
            for _ in range(2)]
    assert all(r.returncode == 0 for r in runs), runs[0].stderr
    lines = [r.stdout.strip().splitlines()[-2:] for r in runs]
    assert lines[0] == lines[1] == ["None", runtime.DEFAULT_CACHE_DIR]


def test_only_the_runtime_module_names_the_cache_dir():
    users = [os.path.relpath(p, REPO) for p in _program_sources()
             if "jax_compilation_cache_dir" in open(p).read()]
    assert users == ["diamond_types_tpu/tpu/runtime.py"]


def test_compile_stats_count_compilations():
    import jax
    import jax.numpy as jnp
    from diamond_types_tpu.tpu.runtime import COMPILE_STATS, first_touch
    first_touch()                                   # installs listeners
    base = COMPILE_STATS.snapshot()
    f = jax.jit(lambda x: (x * 3 - 1).sum())
    jax.block_until_ready(f(jnp.arange(11)))
    jax.block_until_ready(f(jnp.arange(11)))        # cached in process
    d = COMPILE_STATS.delta(COMPILE_STATS.snapshot(), base)
    assert d["compiles"] >= 1 and d["compile_s"] > 0
    again = COMPILE_STATS.snapshot()
    jax.block_until_ready(f(jnp.arange(11)))
    assert COMPILE_STATS.delta(COMPILE_STATS.snapshot(),
                               again)["compiles"] == 0


# ---- failures that used to vanish -----------------------------------------------

def test_warmup_compile_failure_surfaces(monkeypatch):
    from diamond_types_tpu.obs.recorder import FlightRecorder
    from diamond_types_tpu.serve import ServeMetrics, SessionBank
    from diamond_types_tpu.tpu import flush_fuse as ff

    def refuse(**kw):
        raise RuntimeError("Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(ff, "warmup_fused_cache", refuse)
    m = ServeMetrics(1, flush_docs=4, max_pending=16)
    bank = SessionBank(0, engine="device", metrics=m, warmup=True)
    bank.recorder = FlightRecorder()
    with pytest.raises(RuntimeError, match="warm-up failed.*Mosaic"):
        bank.join_warmup(timeout=60)
    assert m.shard[0]["warmup_errors"] == 1
    # and at the front door: serve() refuses to start
    from diamond_types_tpu.tools.server import serve
    with pytest.raises(RuntimeError, match="warm-up failed.*Mosaic"):
        serve(port=0, serve_shards=1, engine="device")


def test_pump_loop_exception_is_counted_not_eaten(capsys):
    from diamond_types_tpu.obs import Observability
    from diamond_types_tpu.serve import MergeScheduler
    sched = MergeScheduler(1, resolve=lambda d: None, engine="host")
    sched.attach_obs(Observability())
    calls = []

    def boom(*a, **k):
        calls.append(1)
        raise RuntimeError("pump exploded")

    sched._pump = boom           # the loop's turn (`pump()` and its pause)
    sched.start_pump(interval_s=0.005)
    deadline = time.monotonic() + 5
    while len(calls) < 3 and time.monotonic() < deadline:
        time.sleep(0.01)
    sched._pump = lambda *a, **k: (0, 0.0)
    sched.stop_pump(drain=False)
    assert len(calls) >= 3                          # the loop kept going
    m = sched.metrics_json()
    assert m["totals"]["pump_errors"] == len(calls)
    ev = [e for e in sched.obs.recorder.dump() if e["kind"] == "pump_error"]
    assert ev and ev[0]["where"] == "pump"
    assert "pump exploded" in ev[0]["error"]
    assert "pump exploded" in capsys.readouterr().err


def test_native_load_failure_raises_unless_python_was_asked_for(
        monkeypatch):
    from diamond_types_tpu import native
    from diamond_types_tpu.native import build, core

    def no_compiler(force=False):
        raise build.NativeBuildError("g++: command not found")

    monkeypatch.setattr(core, "_lib", None)
    monkeypatch.setattr(core, "_load_error", None)
    monkeypatch.setattr(core, "_build", no_compiler)
    monkeypatch.delenv("DT_TPU_NO_NATIVE", raising=False)
    assert core._load() is None                     # library callers degrade
    with pytest.raises(native.NativeUnavailable, match="command not found"):
        native.require_native()                     # the served path does not
    from diamond_types_tpu.tools.server import serve
    with pytest.raises(native.NativeUnavailable):
        serve(port=0)
    monkeypatch.setenv("DT_TPU_NO_NATIVE", "1")     # asked for by name
    assert native.require_native() is False


def test_native_is_loaded_in_this_checkout():
    from diamond_types_tpu.native import require_native
    assert require_native() is True
    tracked = subprocess.run(["git", "ls-files", "native"], cwd=REPO,
                             capture_output=True, text=True)
    if tracked.returncode == 0 and tracked.stdout:  # a git checkout
        assert not [f for f in tracked.stdout.split()
                    if f.endswith((".so", ".stamp"))]


def test_native_stamp_mismatch_rebuilds(tmp_path, monkeypatch):
    from diamond_types_tpu.native import build
    src = tmp_path / "lib.cpp"
    src.write_text('extern "C" int answer() { return 42; }\n')
    out = str(tmp_path / "lib.so")
    cmd = ["g++", "-shared", "-fPIC", str(src)]
    calls = []
    real_run = subprocess.run

    def counting_run(c, **kw):
        calls.append(c)
        return real_run(c, **kw)

    monkeypatch.setattr(build.subprocess, "run", counting_run)
    assert build._build([str(src)], cmd, out, force=False) == out
    assert len(calls) == 1 and os.path.exists(out + ".stamp")
    build._build([str(src)], cmd, out, force=False)
    assert len(calls) == 1                          # stamp matches: trusted
    # a binary built on a machine with other CPU flags is not trusted
    monkeypatch.setattr(build, "_host_cpu_flags", lambda: "sse2 other")
    build._build([str(src)], cmd, out, force=False)
    assert len(calls) == 2
    build._build([str(src)], cmd, out, force=False)
    assert len(calls) == 2
    # nor one that predates a source edit, whatever the mtimes say
    src.write_text('extern "C" int answer() { return 43; }\n')
    os.utime(src, (0, 0))
    build._build([str(src)], cmd, out, force=False)
    assert len(calls) == 3
    # nor one whose stamp is missing (a fresh checkout of an old tree)
    os.unlink(out + ".stamp")
    build._build([str(src)], cmd, out, force=False)
    assert len(calls) == 4


def test_native_build_failure_carries_the_compilers_words(tmp_path):
    from diamond_types_tpu.native import build
    src = tmp_path / "bad.cpp"
    src.write_text("this is not C++\n")
    out = str(tmp_path / "bad.so")
    with pytest.raises(build.NativeBuildError, match="error"):
        build._build([str(src)], ["g++", "-shared", "-fPIC", str(src)],
                     out, force=False)
    assert not os.path.exists(out) and not os.path.exists(out + ".stamp")
    assert [f for f in os.listdir(tmp_path) if f.endswith(".tmp")] == []


# ---- kernels ---------------------------------------------------------------------

def test_apply_op_block_tiled_matches_xla_twin():
    """The lane-tiled step kernel (interpreted here) is byte-identical to
    the XLA formulation — dead lanes included, because the halo blocks
    wrap like jnp.roll — on a row that is one tile (it wraps onto its
    own ends) and across tile edges. One code path for both."""
    import jax.numpy as jnp
    from diamond_types_tpu.tpu.batch import _apply_ops_batched
    from diamond_types_tpu.tpu.pallas_kernels import apply_op_block
    rng = np.random.default_rng(3)
    mi = 16
    for b, cap, tile in ((4, 128, 4096), (8, 512, 128), (5, 1024, 256)):
        docs = rng.integers(1, 1000, size=(b, cap)).astype(np.int32)
        lens = rng.integers(cap // 2, cap - 20, size=b).astype(np.int32)
        for trial in range(12):
            il = rng.integers(0, mi + 1, size=b).astype(np.int32)
            dl = rng.integers(0, mi + 1, size=b).astype(np.int32)
            if trial % 3 == 0:
                il[:] = 0
            if trial % 3 == 1:
                dl[:] = 0
            pos = np.array([rng.integers(0, max(n - d, 0) + 1)
                            for n, d in zip(lens, dl)], np.int32)
            if trial == 4:
                il[:], dl[:] = 0, 0                 # all no-ops
            if trial == 5:
                pos[:] = min(tile, cap) - 3         # straddles a tile edge
            if trial == 6:
                pos[:] = min(tile, cap // 2)        # starts on one
            if trial == 7:
                pos[:] = 0
            chars = rng.integers(1000, 2000, size=(b, mi)).astype(np.int32)
            args = [jnp.asarray(x) for x in (pos, dl, il, chars)]
            ref_d, ref_l = _apply_ops_batched(
                jnp.asarray(docs), jnp.asarray(lens), *args)
            got_d, got_l = apply_op_block(
                *args, jnp.asarray(docs), jnp.asarray(lens),
                interpret=True, tile=tile)
            assert np.array_equal(np.asarray(ref_l), np.asarray(got_l))
            assert np.array_equal(np.asarray(ref_d), np.asarray(got_d)), \
                (b, cap, tile, trial)
            docs = np.asarray(got_d)
            lens = np.clip(np.asarray(got_l), cap // 4,
                           cap - 20).astype(np.int32)
    with pytest.raises(ValueError, match="lane tiles"):
        apply_op_block(*args, jnp.zeros((5, 1000), jnp.int32),
                       jnp.asarray(lens), interpret=True, tile=256)


def test_serve_ladder_pallas_kernels_lower_for_tpu_at_both_classes():
    """Real (interpret=False) Mosaic lowering of a replay scan over
    `apply_op_block`, the hand kernel parked outside the flush path
    (ROADMAP D4), at the note and paper capacity classes: what
    chip_smoke.py's kernel phase runs on the chip lowers here first."""
    import functools
    import jax
    import jax.numpy as jnp
    from diamond_types_tpu.tpu.pallas_kernels import replay_ops_pallas
    b, n, mi = 8, 4, 16
    replay = functools.partial(replay_ops_pallas, interpret=False)
    for cap in (1 << 14, 1 << 18):
        z = jnp.zeros((b, n), jnp.int32)
        text = jax.jit(replay).trace(
            jnp.zeros((b, cap), jnp.int32), jnp.zeros((b,), jnp.int32),
            z, z, z, jnp.zeros((b, n, mi), jnp.int32)
        ).lower(lowering_platforms=("tpu",)).as_text()
        assert "tpu_custom_call" in text


def test_materialize_pallas_refuses_an_oversized_run_table():
    """Above the SMEM bound the kernel raises; it never hands its work to
    the XLA formulation under its own name."""
    import jax.numpy as jnp
    from diamond_types_tpu.tpu import pallas_kernels as pk
    n = pk._SMEM_RUNS_DEFAULT + 1
    z = jnp.zeros((n,), jnp.int32)
    with pytest.raises(ValueError, match="SMEM table bound"):
        pk.materialize_pallas(z, z, z, jnp.zeros((16,), jnp.int32),
                              cap=256, interpret=False)


# ---- the front door ----------------------------------------------------------------

def _post(url, obj):
    req = urllib.request.Request(url, data=json.dumps(obj).encode())
    with urllib.request.urlopen(req, timeout=30) as r:
        return json.loads(r.read())


def test_serve_device_engine_end_to_end(tmp_path):
    """serve(engine="device"): edits over HTTP flush to device sessions;
    the device text equals the host engine's HTTP body, reads are counted
    as device reads, nothing falls back, and a restart on the same data
    dir reads everything back."""
    from diamond_types_tpu.serve import ServeMetrics
    from diamond_types_tpu.tools.server import serve
    so = dict(flush_docs=4, flush_deadline_s=0.02)
    httpd = serve(port=0, data_dir=str(tmp_path), serve_shards=2,
                  engine="device", sched_opts=so)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    docs = [f"doc{i}" for i in range(6)]
    sched = httpd.store.scheduler
    assert sched.banks[0].engine == "device"
    assert {b.device for b in sched.banks} != {None}    # placed
    try:
        heads = {d: [] for d in docs}
        for rnd in range(3):
            for i, d in enumerate(docs):
                r = _post(f"{base}/doc/{d}/edit", {
                    "agent": "w", "version": heads[d],
                    "ops": [{"kind": "ins", "pos": 0,
                             "text": f"r{rnd}d{i} "}]})
                heads[d] = r["version"]
            sched.drain()
        texts = {}
        for d in docs:
            with urllib.request.urlopen(f"{base}/doc/{d}") as r:
                texts[d] = r.read().decode()
            assert sched.text(d) == texts[d] != ""
        with urllib.request.urlopen(f"{base}/metrics") as r:
            m = json.loads(r.read())["serve"]
        assert m["version"] == ServeMetrics.SCHEMA_VERSION
        t = m["totals"]
        assert t["reads_from_device"] == len(docs)
        assert t["reads_from_host"] == 0
        assert (t["host_fallbacks"], t["device_errors"],
                t["warmup_errors"], t["pump_errors"]) == (0, 0, 0, 0)
        assert m["fused"]["device_calls"] + t["syncs"] > 0
        assert all(s.merges >= 1 for b in sched.banks
                   for s in b.sessions.values())
    finally:
        httpd.shutdown()
        httpd.server_close()
    httpd = serve(port=0, data_dir=str(tmp_path), serve_shards=1,
                  engine="host")
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        for d in docs:
            with urllib.request.urlopen(f"{base}/doc/{d}") as r:
                assert r.read().decode() == texts[d]
    finally:
        httpd.shutdown()
        httpd.server_close()


def _serve_mesh_with_edits(tmp_path, n_docs=2):
    """A mesh-window device server holding acknowledged edits that no
    pump has flushed yet (the pump is parked, so the shutdown drain is
    what meets them)."""
    from diamond_types_tpu.tools.server import serve
    httpd = serve(port=0, data_dir=str(tmp_path), serve_shards=2,
                  engine="device",
                  sched_opts=dict(mesh_window=True, flush_docs=4),
                  obs_opts={"sample_rate": 1.0})
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    sched = httpd.store.scheduler
    base = f"http://127.0.0.1:{httpd.server_address[1]}"
    want, heads = {}, {}
    for i in range(n_docs):         # sessions resident first
        heads[i] = _post(f"{base}/doc/d{i}/edit", {
            "agent": "w", "version": [],
            "ops": [{"kind": "ins", "pos": 0, "text": "base "}]})["version"]
    sched.stop_pump(drain=True)
    for i in range(n_docs):
        _post(f"{base}/doc/d{i}/edit", {
            "agent": "w", "version": heads[i],
            "ops": [{"kind": "ins", "pos": 5, "text": f"acked-{i}"}]})
        want[f"d{i}"] = f"base acked-{i}"
    assert sched.queue.total_depth() == n_docs
    return httpd, want


def _read_back(tmp_path, want):
    from diamond_types_tpu.tools.server import serve
    httpd = serve(port=0, data_dir=str(tmp_path), serve_shards=1,
                  engine="host")
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    try:
        base = f"http://127.0.0.1:{httpd.server_address[1]}"
        for d, text in want.items():
            with urllib.request.urlopen(f"{base}/doc/{d}") as r:
                assert r.read().decode() == text
    finally:
        httpd.shutdown()
        httpd.server_close()


def test_device_error_in_the_shutdown_drain_keeps_the_durable_flush(
        tmp_path, monkeypatch):
    """The inline drain re-raises a device error; the clean shutdown's
    final flush must run all the same (an acknowledged edit reads back
    after a restart), and the error must still come out."""
    from diamond_types_tpu.parallel import mesh as pm
    httpd, want = _serve_mesh_with_edits(tmp_path)
    sched = httpd.store.scheduler

    def refuse(*a, **k):
        raise RuntimeError("XLA runtime error: device halted")

    monkeypatch.setattr(pm, "mesh_fused_replay", refuse)
    httpd.shutdown()
    with pytest.raises(RuntimeError, match="device halted"):
        httpd.server_close()
    assert {f for f in os.listdir(tmp_path) if f.endswith(".dt")} \
        == {d + ".dt" for d in want}                 # on disk
    assert all(w is None for w in sched._workers)    # workers joined
    m = sched.metrics_json()
    assert m["totals"]["device_errors"] == 1
    # the window span ended, marked, instead of leaking
    spans = [s for s in httpd.store.obs.tracer.spans()
             if s["name"] == "serve.mesh_window"]
    assert spans and spans[-1]["attrs"]["error"] == "RuntimeError"
    _read_back(tmp_path, want)


def test_mesh_window_winds_up_committed_classes_before_it_raises(
        tmp_path, monkeypatch):
    """Two shape classes in one window, the second replay raises: the
    first class is adopted and accounted, the error comes out after."""
    from diamond_types_tpu.parallel import mesh as pm
    from diamond_types_tpu.serve import MergeScheduler
    from diamond_types_tpu.text.oplog import OpLog
    ols = {}
    for d, n in (("small", 40), ("big", 4000)):
        ol = ols[d] = OpLog()
        ol.add_insert(ol.get_or_create_agent_id("a"), 0, "x" * n)
    sched = MergeScheduler(1, resolve=ols.__getitem__, engine="device",
                           mesh_window=True, flush_workers=False)
    for d in ols:
        sched.submit(d)
    sched.drain()                       # sessions resident, two caps
    caps = {d: sched.banks[0].sessions[d].cap for d in ols}
    assert caps["small"] < caps["big"]
    for d, ol in ols.items():
        ol.add_insert(ol.get_or_create_agent_id("a"), 0, "new ")
        sched.submit(d)
    real = pm.mesh_fused_replay

    def second_class_refuses(mesh, sessions, plans):
        if sessions[0].cap == caps["big"]:
            raise RuntimeError("Mosaic failed to compile TPU kernel")
        return real(mesh, sessions, plans)

    monkeypatch.setattr(pm, "mesh_fused_replay", second_class_refuses)
    invalidated = []
    sched.read_invalidate = invalidated.append
    before = sched.metrics_json()
    with pytest.raises(RuntimeError, match="Mosaic"):
        sched.pump(force=True)
    after = sched.metrics_json()
    assert sched.banks[0].sessions["small"].text().startswith("new ")
    assert not sched.banks[0].sessions["big"].text().startswith("new ")
    w0, w1 = before["window"], after["window"]
    assert w1["dispatches"] - w0["dispatches"] == 1
    assert w1["shape_classes"] - w0["shape_classes"] == 2
    assert after["totals"]["flushes"] > before["totals"]["flushes"]
    assert after["totals"]["device_errors"] == 1
    assert "small" in invalidated
    # nothing is lost: the next flush re-plans the row that never ran
    monkeypatch.setattr(pm, "mesh_fused_replay", real)
    sched.submit("big")
    sched.drain()
    assert sched.text("big") == ols["big"].checkout_tip().snapshot()


def test_pump_error_is_filed_under_the_failing_shard():
    from diamond_types_tpu.serve import MergeScheduler, ServeMetrics
    from diamond_types_tpu.serve.bank import SessionBank
    sched = MergeScheduler(3, resolve=lambda d: None, engine="host")
    e = RuntimeError("boom")
    sched.banks[2].device_error("mesh", e)
    sched._loop_error("pump", 0, e)
    sched._loop_error("pump", 0, RuntimeError("untagged"))
    assert [s["pump_errors"] for s in sched.metrics.shard] == [1, 0, 1]


def test_group_fence_completes_when_the_drain_raises():
    """A demotion fence is a safety action: the registration goes even
    if the inline drain raised, and the error still surfaces."""
    from diamond_types_tpu.replicate.node import ReplicaNode

    class _Sched:
        def drain(self):
            raise RuntimeError("device halted")

    class _Groups:
        dropped = []

        def get(self, doc_id):
            return None

        def drop(self, doc_id, at_or_below=None):
            self.dropped.append((doc_id, at_or_below))

    node = ReplicaNode.__new__(ReplicaNode)
    node.writergroups = _Groups()
    node._group_demote_drains = True
    node.store = type("S", (), {"scheduler": _Sched()})()
    with pytest.raises(RuntimeError, match="device halted"):
        node._group_fence_local("doc", 7)
    assert node.writergroups.dropped == [("doc", 7)]


def test_chip_smoke_tiny_preflight_on_the_named_cpu():
    r = _smoke(["--tiny"], env={
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2"})
    assert r.returncode == 0, r.stdout[-3000:] + r.stderr[-3000:]
    last = json.loads(r.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "on_chip": False,
                    "device": {"platform": "cpu", "kind": "cpu",
                               "count": 2}}
    assert '"reduced"' in r.stdout and '"assumed"' in r.stdout
    assert "three-way equality holds" in r.stdout
    assert "all 16 documents read back after the restart" in r.stdout


def test_chip_smoke_fails_without_a_chip():
    """No TPU: non-zero and no result line — whether the CPU was named
    (the full size runs on a TPU only) or not (the first-touch guard)."""
    named = _smoke([], env={"JAX_PLATFORMS": "cpu"})
    unnamed = _smoke(["--tiny"], drop=("JAX_PLATFORMS",))
    for r, why in ((named, "runs on a TPU only"), (unnamed, "NoAccelerator")):
        assert r.returncode != 0
        assert why in r.stdout + r.stderr
        assert '"ok"' not in r.stdout


def test_chip_smoke_alone_in_a_directory_fails(tmp_path):
    import shutil
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    r = _python(["chip_smoke.py", "--tiny"], drop=("PYTHONPATH",),
                cwd=tmp_path)
    assert r.returncode != 0 and '"ok"' not in r.stdout
    assert "needs the diamond_types_tpu checkout" in r.stderr
