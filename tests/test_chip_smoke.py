"""The chip path's seams, without a chip and without an XLA compile.

``chip_smoke.py`` is the one program that runs on the TPU; these tests
pin what has to hold for its verdict to mean anything: the worker
refuses to serve ``--backend tpu`` from the CPU backend unless asked to,
the parent spawns it with the environment untouched and its stderr
open, a failed flush prints why, the compile cache goes where the
environment says, and the smoke's own checks (verdicts, fallbacks,
flush counts, the worker's device) fail loudly.  Control flow is driven
against a ``batched`` worker on the scalar suite: seconds, no jax.
"""

import json
import os
import shutil
import socket
import subprocess
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from hbbft_tpu.crypto.backend import CryptoBackend  # noqa: E402
from hbbft_tpu.crypto.suite import ScalarSuite  # noqa: E402
from hbbft_tpu.cryptoplane import proc_service  # noqa: E402
from hbbft_tpu.cryptoplane.proc_service import ServiceProcess  # noqa: E402
from hbbft_tpu.cryptoplane.service import CryptoPlaneService  # noqa: E402
from hbbft_tpu.transport.framing import (  # noqa: E402
    CRYPTO_KINDS,
    KIND_CRYPTO_RESP,
    FrameDecoder,
    encode_frame,
)
from hbbft_tpu.utils import jaxcache  # noqa: E402


# -- compile cache ----------------------------------------------------------

@pytest.fixture
def config_updates(monkeypatch):
    import jax

    seen = {}
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: seen.__setitem__(name, value)
    )
    return seen


def test_enable_cache_leaves_the_directory_to_the_environment(
    monkeypatch, config_updates, tmp_path
):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cc"))
    jaxcache.enable_cache()
    assert "jax_compilation_cache_dir" not in config_updates
    assert config_updates["jax_persistent_cache_min_compile_time_secs"] == 1.0
    assert not (tmp_path / "cc").exists()  # jax makes it, not this code


def test_enable_cache_defaults_to_the_checkout(monkeypatch, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jaxcache.enable_cache()
    assert config_updates["jax_compilation_cache_dir"] == os.path.join(
        ROOT, ".jax_cache"
    )


# -- the worker's device check ----------------------------------------------

def _fake_device(monkeypatch, platform):
    monkeypatch.setattr(
        proc_service, "_jax_device",
        lambda: {"platform": platform, "kind": platform, "count": 1},
    )


def test_tpu_backend_refuses_the_cpu_platform(monkeypatch):
    _fake_device(monkeypatch, "cpu")
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(RuntimeError, match="found no TPU"):
        proc_service._build_backend("tpu", None)


@pytest.mark.parametrize(
    "platform,env", [("cpu", "cpu"), ("tpu", None)],
    ids=["cpu-asked-for", "tpu"],
)
def test_tpu_backend_accepts(monkeypatch, platform, env):
    from hbbft_tpu.crypto.tpu import TpuBackend

    _fake_device(monkeypatch, platform)
    if env is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", env)
    assert isinstance(proc_service._build_backend("tpu", None), TpuBackend)


def test_ready_fields_report_the_device_and_the_cache():
    import jax

    got = proc_service._jax_ready_fields()
    assert got["device"] == {
        "platform": "cpu", "kind": "cpu", "count": len(jax.devices()),
    }
    assert got["jax"] == jax.__version__
    assert got["compile_cache_dir"] == jax.config.jax_compilation_cache_dir
    assert isinstance(got["compile_cache_empty"], bool)


# -- the parent's spawn -----------------------------------------------------

class _FakePopen:
    def __init__(self, cmd, **kw):
        self.cmd, self.kw = cmd, kw
        self.stdout = iter(())
        self.stdin = None
        self.returncode = 0

    def poll(self):
        return 0

    def wait(self, timeout=None):
        return 0


@pytest.mark.parametrize(
    "backend,stderr", [("tpu", None), ("batched", subprocess.DEVNULL)]
)
def test_spawn_passes_the_environment_through(monkeypatch, backend, stderr):
    spawned = []
    monkeypatch.setattr(
        proc_service.subprocess, "Popen",
        lambda cmd, **kw: spawned.append(_FakePopen(cmd, **kw)) or spawned[-1],
    )
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    proc = ServiceProcess(suite="bls", backend=backend)
    proc._spawn(0)
    proc.stop()
    (p,) = spawned
    assert p.cmd[p.cmd.index("--backend") + 1] == backend
    assert "JAX_PLATFORMS" not in p.kw["env"]
    assert p.kw["env"]["JAX_COMPILATION_CACHE_DIR"] == "/somewhere/else"
    assert p.kw["env"]["PYTHONPATH"] == ROOT
    # a tpu worker's stderr is the parent's; the others stay quiet
    assert p.kw["stderr"] is stderr


def test_start_fails_at_once_when_the_worker_exits_before_ready():
    proc = ServiceProcess(python="false", ready_timeout_s=60.0)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError, match="rc=1"):
        proc.start()
    assert time.monotonic() - t0 < 30.0


def test_ready_line_of_a_jaxless_worker_has_no_device():
    with ServiceProcess(suite="scalar", backend="batched") as proc:
        assert proc.ready["device"] is None
        assert "jax" not in proc.ready


# -- failures that speak ----------------------------------------------------

def test_failed_flush_prints_its_traceback_and_is_counted(capsys):
    class Broken(CryptoBackend):
        def verify_batch(self, reqs):
            raise MemoryError("RESOURCE_EXHAUSTED: out of HBM")

    service = CryptoPlaneService(Broken(), window_s=0.0).start()
    try:
        job = service.submit([object()])
        assert job.done.wait(10.0)
    finally:
        service.stop()
    assert job.results is None  # the client falls back
    assert service.metrics.counters["crypto.flush_errors"] == 1
    err = capsys.readouterr().err
    assert "Traceback" in err and "RESOURCE_EXHAUSTED: out of HBM" in err


def test_recv_frame_waits_out_its_deadline_not_a_slice_of_it():
    """A response slower than 5 s is not a timeout while the deadline is
    further off (a cold compile is minutes)."""
    a, b = socket.socketpair()
    frame = encode_frame(KIND_CRYPTO_RESP, b"late", kinds=CRYPTO_KINDS)
    sender = threading.Timer(5.3, b.sendall, (frame,))
    sender.start()
    try:
        got = proc_service._recv_frame(
            a, FrameDecoder(kinds=CRYPTO_KINDS), time.monotonic() + 30.0
        )
    finally:
        sender.join()
        a.close()
        b.close()
    assert got == (KIND_CRYPTO_RESP, b"late")


# -- chip_smoke's own checks ------------------------------------------------

@pytest.fixture(scope="module")
def scalar_phases():
    suite = ScalarSuite()
    phases = chip_smoke.make_phases(11, suite)
    assert [(p.name, len(p.reqs), sum(p.expected)) for p in phases] == [
        ("round", 16, 11),
    ]
    # the construction agrees with the oracle (same check main() makes)
    assert [chip_smoke.check_reference(suite, p) for p in phases] == [None]
    return suite, phases


def test_phases_follow_the_seed(scalar_phases):
    suite, phases = scalar_phases
    (again,) = chip_smoke.make_phases(11, suite)
    (other,) = chip_smoke.make_phases(12, suite)
    assert again.expected == phases[0].expected
    assert again.reqs == phases[0].reqs
    assert other.expected != phases[0].expected
    assert chip_smoke.check_reference(
        suite, again._replace(expected=other.expected)
    ).startswith("round: oracle says")


def test_drive_passes_against_a_healthy_worker(scalar_phases):
    suite, phases = scalar_phases
    with ServiceProcess(suite="scalar", backend="batched") as proc:
        rows, failures = chip_smoke.drive(proc, suite, phases, timeout_s=60.0)
    assert failures == []
    assert [(r["phase"], r["requests"], r["bucket"]) for r in rows] == [
        ("round", 16, [16, 16, 2]),
    ]
    for r in rows:
        assert set(r["host_wall_s"]) == {
            "first_call", "repeat_call",
            "first_call_worker_flush", "repeat_call_worker_flush",
        }
        assert all(v > 0 for v in r["host_wall_s"].values())
    assert chip_smoke.worker_exit(proc) is None


def test_drive_fails_on_a_wrong_verdict(scalar_phases):
    suite, (round_,) = scalar_phases
    lying = round_._replace(expected=[True] * 16)
    with ServiceProcess(suite="scalar", backend="batched") as proc:
        rows, failures = chip_smoke.drive(proc, suite, [lying], timeout_s=60.0)
    assert rows == []
    assert len(failures) == 1 and "5 verdicts differ" in failures[0]


def test_drive_fails_on_a_counted_fallback(scalar_phases):
    """A dead worker: the client's local backend answers (correctly), and
    that is exactly what must not pass."""
    suite, (round_,) = scalar_phases
    proc = ServiceProcess(suite="scalar", backend="batched").start()
    try:
        proc.kill()
        proc.proc.wait(timeout=10)
        rows, failures = chip_smoke.drive(proc, suite, [round_], timeout_s=60.0)
    finally:
        exit_failure = chip_smoke.worker_exit(proc)
    assert rows == []
    assert any("fell back" in f and "crypto.rpc.fallbacks" in f for f in failures)
    assert any("worker died" in f for f in failures)
    assert exit_failure == "worker exit code -9"


def test_drive_fails_when_the_worker_counts_a_flush_error(scalar_phases):
    """The worker's own flush failed (the client fell back to a right
    answer): both counters are reported."""
    suite, (round_,) = scalar_phases

    class Broken(CryptoBackend):
        def verify_batch(self, reqs):
            raise RuntimeError("Mosaic failed to compile TPU kernel")

    server = proc_service.CryptoRpcServer(
        CryptoPlaneService(Broken(), window_s=0.0), suite
    ).start()

    class InThread:  # the ServiceProcess surface that drive() uses
        addr = (server.host, server.port)
        alive = True
        ready = {"ready": True, "device": None}

        def stats(self):
            return proc_service.fetch_stats(self.addr, suite)

    try:
        rows, failures = chip_smoke.drive(
            InThread(), suite, [round_], timeout_s=60.0
        )
    finally:
        server.stop()
    assert rows == []
    assert any("crypto.rpc.fallback.flush-failed" in f for f in failures)
    assert any("1 flush errors" in f for f in failures)
    assert any("0 flushes after 1 calls" in f for f in failures)


# -- the script itself, from what git would commit --------------------------

@pytest.fixture(scope="module")
def bare_checkout(tmp_path_factory):
    """chip_smoke.py, the package and the native SOURCES — no
    ``native/build/``, no ``.jax_cache/``: what the driver's checkout of
    the commit holds."""
    dst = tmp_path_factory.mktemp("checkout")
    skip = shutil.ignore_patterns("__pycache__", "build", "*.so", "*.o")
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), dst)
    for sub in ("hbbft_tpu", "native"):
        shutil.copytree(os.path.join(ROOT, sub), dst / sub, ignore=skip)
    return dst


def _artefacts(root):
    return sorted(
        os.path.join(d, f)
        for d, _, files in os.walk(root)
        for f in files
        if f.endswith((".so", ".o"))
    )


def test_parent_imports_no_jax_and_builds_nothing(bare_checkout):
    code = (
        "import sys, chip_smoke as cs\n"
        "from hbbft_tpu.crypto.suite import ScalarSuite\n"
        "cs.make_phases(0, ScalarSuite())\n"
        "bad = [m for m in sys.modules if m == 'jax' or m.startswith('jax.')"
        " or m == 'jaxlib']\n"
        "assert not bad, bad\n"
    )
    r = subprocess.run(
        [sys.executable, "-c", code], cwd=bare_checkout,
        capture_output=True, text=True, timeout=120,
    )
    assert r.returncode == 0, r.stderr
    assert not (bare_checkout / "native" / "build").exists()
    assert _artefacts(bare_checkout) == []


def test_script_refuses_a_worker_that_holds_no_tpu(bare_checkout):
    """End to end on this box: the worker starts on the CPU backend
    (asked for through the environment), says so in its ready line, and
    the script prints no result and exits non-zero — before any flush,
    so nothing compiles."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    r = subprocess.run(
        [sys.executable, "chip_smoke.py", "--seed", "3"], cwd=bare_checkout,
        env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode == 1
    assert r.stdout == ""
    assert "holds no TPU" in r.stderr and "'platform': 'cpu'" in r.stderr
    # the worker had its cache on before any jit, at the fixed path
    assert (bare_checkout / ".jax_cache").is_dir()
    assert not (bare_checkout / "native" / "build").exists()
    assert _artefacts(bare_checkout) == []


def test_last_line_is_the_contract_and_nothing_more(monkeypatch, capsys):
    """main() with the worker faked as a TPU holder: phase rows first,
    then exactly {"ok", "device"} built from the worker's ready line."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    suite = ScalarSuite()

    class FakeProc:
        def __init__(self, **kw):
            assert kw["suite"] == "bls" and kw["backend"] == "tpu"
            self.real = ServiceProcess(suite="scalar", backend="batched")

        def start(self):
            self.real.start()
            self.real.ready["device"] = device
            return self

        def __getattr__(self, name):
            return getattr(self.real, name)

    monkeypatch.setattr(chip_smoke, "ServiceProcess", FakeProc)
    monkeypatch.setattr(chip_smoke, "BLSSuite", lambda: suite)
    assert chip_smoke.main(["--seed", "5"]) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    assert [json.loads(ln)["phase"] for ln in lines[:-1]] == ["round"]
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
