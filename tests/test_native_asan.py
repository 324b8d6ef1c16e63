"""AddressSanitizer twin of tests/test_native_tsan.py for the native
decoder (native/jsoncol.cpp).

TSAN proves the GIL-free shard fan-out is race-free; ASAN proves its
MEMORY discipline: the shard parse writes disjoint row slices of one
shared allocation (an off-by-one there is a heap-buffer-overflow TSAN
cannot see), and the keytab encode's appendix-append + mid-batch
rollback path frees/reuses table storage whose misuse would be a
use-after-free. The test builds `make asan` (mtime-cached), then drives
multi-shard decodes — including the bad-row and string-cast paths, whose
error handling is where buffer math historically goes wrong — plus
keytab encodes across a growing table, inside a subprocess running
under libasan, and fails on any AddressSanitizer report.

Skips with an explicit reason when the sanitizer toolchain is missing
(no g++/make, no libasan, or the instrumented build fails) — the suite
must stay green on minimal images. docs/STATIC_ANALYSIS.md § Sanitizer
builds.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
NATIVE = REPO / "native"
ASAN_SO = NATIVE / "build" / "asan" / "ekjsoncol.so"

# the stress driver runs inside the ASAN-preloaded subprocess; kept as a
# string so the test file itself never imports the instrumented module
DRIVER = r"""
import sys
sys.path.insert(0, sys.argv[1])  # build/asan — shadows any regular build
import ekjsoncol

ROWS = [
    (b'{"dev": "sensor-%d", "temp": %d.5, "n": %d, "ok": true}'
     % (i % 13, i % 90, i)) for i in range(4096)
]
SPEC = (("temp", 0), ("n", 1), ("ok", 2), ("dev", 3))
BAD = list(ROWS)
BAD[17] = b'{"temp": not-json'             # bad-row marking across shards
BAD[4090] = b'{"dev": "x", "temp": "4.25"}'  # string->float cast path
BAD[-1] = b'{"dev": "' + b'x' * 5000 + b'"}'  # oversized string tail

for shards in (1, 2, 4):
    for _ in range(3):
        cols, valid, bad, _ = ekjsoncol.decode(ROWS, SPEC, shards)
        assert not bad.any()
        cols, valid, bad, _ = ekjsoncol.decode(BAD, SPEC, shards)
        assert bad[17] and not bad[4090]

tab = ekjsoncol.keytab_new()
seen = 0
for round_ in range(6):
    # growing key population: appendix append + storage growth; the
    # surrogate/fallback rows exercise the no-mutate rollback path
    keys = [f"dev-{i % (257 * (round_ + 1))}" for i in range(4096)]
    slots, appendix = ekjsoncol.keytab_encode(tab, keys)
    assert len(slots) == len(keys)
    seen += len(appendix)
    try:
        ekjsoncol.keytab_encode(tab, ["ok", 42, "also-ok"])
    except Exception:
        pass  # non-str key: must roll back without touching storage

# the int64 key table: growth rehashes (1,024 cells up), a pass that runs
# out of slot ids after taking keys in (every one rolled back by
# backward-shift deletion), refused loads, then the survivors read again
import numpy as np

itab = ekjsoncol.keytab_i64_new()
rng = np.random.default_rng(3)
known = {}
for round_ in range(6):
    col = rng.integers(-2**63, 2**63 - 1, 3000 * (round_ + 1), dtype=np.int64)
    col[::3] = col[0]
    slots, appendix = ekjsoncol.keytab_encode_i64(itab, col, len(known))
    for k in appendix.tolist():
        known[k] = len(known)
    assert slots.tolist() == [known[k] for k in col.tolist()]
    fresh = rng.integers(-2**63, 2**63 - 1, 5000, dtype=np.int64)
    fresh[::2] = col[:2500]
    try:
        ekjsoncol.keytab_encode_i64(itab, fresh, 2**31 - 100)
        raise SystemExit("slot ids past int32 were handed out")
    except OverflowError:
        pass
    try:
        ekjsoncol.keytab_load_i64(
            itab, np.append(fresh[1:400:2], col[0]),
            np.arange(201, dtype=np.int32) + len(known))
        raise SystemExit("a key took a second slot")
    except ValueError:
        pass
    slots, appendix = ekjsoncol.keytab_encode_i64(itab, col, len(known))
    assert len(appendix) == 0
    assert slots.tolist() == [known[k] for k in col.tolist()]
print("ASAN_STRESS_OK", seen, len(known))
"""


def _libasan() -> str:
    """Absolute path of libasan, or '' when the toolchain can't provide
    it (g++ echoes the bare name back when the library is unknown)."""
    gxx = shutil.which("g++")
    if gxx is None:
        return ""
    for name in ("libasan.so", "libasan.so.6", "libasan.so.8",
                 "libasan.so.5"):
        try:
            out = subprocess.run(
                [gxx, f"-print-file-name={name}"], capture_output=True,
                text=True, timeout=30).stdout.strip()
        except (OSError, subprocess.TimeoutExpired):
            return ""
        if out and out != name and os.path.exists(out):
            return out
    return ""


def _ensure_asan_build() -> None:
    """`make asan`, cached on source mtime like the TSAN build."""
    src = NATIVE / "jsoncol.cpp"
    if ASAN_SO.exists() and ASAN_SO.stat().st_mtime >= src.stat().st_mtime:
        return
    proc = subprocess.run(
        ["make", "-C", str(NATIVE), "asan", f"PYTHON={sys.executable}"],
        capture_output=True, text=True, timeout=300)
    if proc.returncode != 0 or not ASAN_SO.exists():
        pytest.skip("sanitizer build failed — no ASAN coverage on this "
                    f"toolchain:\n{proc.stdout}\n{proc.stderr}")


def test_shard_parse_keytab_memory_safe():
    if not shutil.which("g++") or not shutil.which("make"):
        pytest.skip("no g++/make — sanitizer toolchain not present")
    libasan = _libasan()
    if not libasan:
        pytest.skip("g++ has no libasan — sanitizer runtime not present")
    _ensure_asan_build()

    env = dict(os.environ)
    # preload: the instrumented .so needs the ASAN runtime resident
    # before the (uninstrumented) python binary maps it
    env["LD_PRELOAD"] = libasan
    # leak detection off: CPython itself "leaks" interned/static
    # allocations at exit, which would drown real reports; the target
    # classes here (overflow, use-after-free) abort at the fault site
    env["ASAN_OPTIONS"] = ("detect_leaks=0:abort_on_error=0:"
                           "exitcode=66:allocator_may_return_null=1")
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, str(ASAN_SO.parent)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(REPO))
    report = f"rc={proc.returncode}\n{proc.stdout}\n{proc.stderr}"
    assert "ERROR: AddressSanitizer" not in report, (
        "memory fault in the native shard parse/keytab path:\n" + report)
    assert proc.returncode == 0 and "ASAN_STRESS_OK" in proc.stdout, (
        "ASAN stress driver did not complete cleanly:\n" + report)
