"""Tier-1 build smoke for the native WAL engine: compile wal.cpp from
scratch with the same flags the lazy builder uses, assert the result
loads and exports the full surface (classic framing + the host-tier
stage/pack entry points), and drive one tiny raw-ctypes round trip.
Skips cleanly when the toolchain is absent — the pure-Python engine is
the portable fallback and has its own suites."""

import ctypes
import os
import shutil
import subprocess

import pytest

from rafting_tpu.log import wal as wal_mod

_HAVE_GXX = shutil.which("g++") is not None

pytestmark = pytest.mark.skipif(not _HAVE_GXX,
                                reason="no C++ toolchain on this host")


@pytest.fixture(scope="module")
def fresh_so(tmp_path_factory):
    """Compile wal.cpp into a module-scoped scratch .so (never the
    committed one — a broken build must not poison other suites)."""
    d = tmp_path_factory.mktemp("native-build")
    so = str(d / "libwal_smoke.so")
    r = subprocess.run(
        ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-pthread",
         wal_mod._SRC, "-o", so],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, f"wal.cpp failed to compile:\n{r.stderr[-2000:]}"
    return so


def test_fresh_so_exports_full_surface(fresh_so):
    lib = ctypes.CDLL(fresh_so)
    for sym in ("wal_open", "wal_close", "wal_append_entry",
                "wal_append_stable", "wal_truncate", "wal_milestone",
                "wal_sync", "wal_tail", "wal_floor", "wal_error",
                "wal_stage_and_sync", "wal_pack_ae", "wal_buf_free",
                "wal_fault_set", "wal_fault_clear", "wal_poisoned",
                "wal_last_errno"):
        assert hasattr(lib, sym), f"missing export: {sym}"


def test_fresh_so_round_trip(fresh_so, tmp_path):
    """Raw ctypes against the freshly built .so: open, append, sync,
    reopen, read back — the build is functional, not just linkable."""
    lib = ctypes.CDLL(fresh_so)
    lib.wal_open.restype = ctypes.c_void_p
    lib.wal_open.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
    lib.wal_close.argtypes = [ctypes.c_void_p]
    lib.wal_append_entry.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint64, ctypes.c_int64,
        ctypes.c_char_p, ctypes.c_uint32]
    lib.wal_sync.argtypes = [ctypes.c_void_p]
    lib.wal_sync.restype = ctypes.c_int
    lib.wal_tail.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
    lib.wal_tail.restype = ctypes.c_int64

    d = str(tmp_path / "w").encode()
    h = lib.wal_open(d, 1 << 20)
    assert h
    lib.wal_append_entry(h, 0, 1, 7, b"smoke", 5)
    assert lib.wal_sync(h) == 0
    lib.wal_close(h)
    h = lib.wal_open(d, 1 << 20)
    assert h and lib.wal_tail(h, 0) == 1
    lib.wal_close(h)


def test_binding_loads_the_build_of_the_committed_source():
    """The in-repo binding (which builds/loads lazily on first use) must
    load when a toolchain exists, from an artefact named after the hash
    of wal.cpp — never a binary some other source produced."""
    import hashlib
    assert wal_mod.native_available(), wal_mod._build_err
    with open(wal_mod._SRC, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    assert os.path.basename(wal_mod._lib._name) == f"libwal-{digest}.so"


_SAN_FLAGS = ["-fsanitize=address,undefined", "-fno-sanitize-recover=all",
              "-g", "-O1"]


def _have_sanitizers(scratch) -> bool:
    """Probe: can this toolchain build AND run a sanitized binary?  Some
    containers ship g++ without libasan/libubsan, or block the ptrace
    ASan needs — skip rather than fail there."""
    src = scratch / "probe.cpp"
    src.write_text("int main() { return 0; }\n")
    exe = str(scratch / "probe")
    r = subprocess.run(["g++", *_SAN_FLAGS, str(src), "-o", exe],
                       capture_output=True, text=True, timeout=120)
    if r.returncode != 0:
        return False
    r = subprocess.run([exe], capture_output=True, timeout=60,
                       env={**os.environ, "ASAN_OPTIONS": "detect_leaks=0"})
    return r.returncode == 0


def test_native_fault_smoke_under_sanitizers(tmp_path):
    """Build wal.cpp + the fault-smoke driver under ASan/UBSan and run
    the injected-fault scenarios (fail-stop fsync, retriable ENOSPC,
    torn write) as a standalone executable — a sanitized .so cannot be
    dlopen'd into this unsanitized pytest process, so the smoke runs out
    of process.  Catches allocator misuse / UB on the exact error paths
    the storage nemesis exercises."""
    if not _have_sanitizers(tmp_path):
        pytest.skip("sanitizer runtime unavailable on this host")
    driver = os.path.join(os.path.dirname(__file__),
                          "native_fault_smoke.cpp")
    exe = str(tmp_path / "fault_smoke")
    r = subprocess.run(
        ["g++", *_SAN_FLAGS, "-std=c++17", "-pthread",
         wal_mod._SRC, driver, "-o", exe],
        capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, \
        f"sanitized build failed:\n{r.stderr[-2000:]}"
    scratch = tmp_path / "wal-scratch"
    scratch.mkdir()
    r = subprocess.run(
        [exe, str(scratch)], capture_output=True, text=True, timeout=120,
        env={**os.environ, "ASAN_OPTIONS": "detect_leaks=0"})
    assert r.returncode == 0, \
        f"fault smoke failed (rc={r.returncode}):\n" \
        f"{r.stdout[-1000:]}\n{r.stderr[-3000:]}"
