"""ThreadSanitizer stress for the native shard parser (native/jsoncol.cpp).

The GIL-free decode pass fans N std::threads over ONE shared set of
output allocations (disjoint row slices of the same numpy buffers) and
had zero sanitizer coverage before this suite: a torn write there would
corrupt columns silently, and only on multi-shard configs. The test
builds the `make tsan` module, then stress-drives multi-shard decodes
from several Python threads (plus keytab encodes, whose appendix/commit
path shares the table across batches, and int64 key-table passes: two
tables from two threads beside the decodes' shard threads) in a
subprocess running under libtsan, and fails on any ThreadSanitizer
report.

Skips with an explicit reason when the sanitizer toolchain is missing
(no g++, no libtsan, or the instrumented build fails) — the suite must
stay green on minimal images. docs/STATIC_ANALYSIS.md § Sanitizer builds.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
NATIVE = REPO / "native"
TSAN_SO = NATIVE / "build" / "tsan" / "ekjsoncol.so"

# the stress driver runs inside the TSAN-preloaded subprocess; kept as a
# string so the test file itself never imports the instrumented module
DRIVER = r"""
import sys, threading
sys.path.insert(0, sys.argv[1])  # build/tsan — shadows any regular build
import ekjsoncol

ROWS = [
    (b'{"dev": "sensor-%d", "temp": %d.5, "n": %d, "ok": true}'
     % (i % 13, i % 90, i)) for i in range(4096)
]
SPEC = (("temp", 0), ("n", 1), ("ok", 2), ("dev", 3))
BAD = list(ROWS)
BAD[17] = b'{"temp": not-json'            # bad-row marking across shards
BAD[4090] = b'{"dev": "x", "temp": "4.25"}'  # string->float cast path

errs = []

def decode_loop():
    try:
        for _ in range(6):
            cols, valid, bad, _ = ekjsoncol.decode(ROWS, SPEC, 4)
            assert not bad.any()
            cols, valid, bad, _ = ekjsoncol.decode(BAD, SPEC, 4)
            assert bad[17] and not bad[4090]
    except BaseException as exc:  # noqa: BLE001 - surfaced below
        errs.append(exc)

def keytab_loop():
    try:
        tab = ekjsoncol.keytab_new()
        keys = [f"dev-{i % 257}" for i in range(4096)]
        for _ in range(6):
            slots, appendix = ekjsoncol.keytab_encode(tab, keys)
            assert len(slots) == len(keys)
    except BaseException as exc:  # noqa: BLE001
        errs.append(exc)

# the int64 key table: two tables from two threads, their passes (growth
# included) beside the decodes' shard threads
import numpy as np

INT_KEYS = np.random.default_rng(7).integers(-2**62, 2**62, 20000)

def i64_loop(seed):
    try:
        rng = np.random.default_rng(seed)
        tab = ekjsoncol.keytab_i64_new()
        known = {}
        for _ in range(8):
            col = INT_KEYS[rng.integers(0, len(INT_KEYS), 200000)]
            slots, appendix = ekjsoncol.keytab_encode_i64(
                tab, col, len(known))
            for k in appendix.tolist():
                known[k] = len(known)
            probe = rng.integers(0, len(col), 64)
            assert [known[int(col[i])] for i in probe] == \
                slots[probe].tolist()
    except BaseException as exc:  # noqa: BLE001
        errs.append(exc)

threads = [threading.Thread(target=decode_loop) for _ in range(3)]
threads.append(threading.Thread(target=keytab_loop))
threads += [threading.Thread(target=i64_loop, args=(s,)) for s in (1, 2)]
for t in threads:
    t.start()
for t in threads:
    t.join()
if errs:
    raise SystemExit(f"stress driver failed: {errs[0]!r}")
print("TSAN_STRESS_OK")
"""


def _libtsan() -> str:
    """Absolute path of libtsan, or '' when the toolchain can't provide
    it (g++ echoes the bare name back when the library is unknown)."""
    gxx = shutil.which("g++")
    if gxx is None:
        return ""
    for name in ("libtsan.so", "libtsan.so.0", "libtsan.so.2"):
        try:
            out = subprocess.run(
                [gxx, f"-print-file-name={name}"], capture_output=True,
                text=True, timeout=30).stdout.strip()
        except (OSError, subprocess.TimeoutExpired):
            return ""
        if out and out != name and os.path.exists(out):
            return out
    return ""


def _ensure_tsan_build() -> None:
    """`make tsan`, cached on source mtime like check_native's build."""
    src = NATIVE / "jsoncol.cpp"
    if TSAN_SO.exists() and TSAN_SO.stat().st_mtime >= src.stat().st_mtime:
        return
    proc = subprocess.run(
        ["make", "-C", str(NATIVE), "tsan", f"PYTHON={sys.executable}"],
        capture_output=True, text=True, timeout=300)
    if proc.returncode != 0 or not TSAN_SO.exists():
        pytest.skip("sanitizer build failed — no TSAN coverage on this "
                    f"toolchain:\n{proc.stdout}\n{proc.stderr}")


def test_shard_parse_keytab_race_free():
    if not shutil.which("g++") or not shutil.which("make"):
        pytest.skip("no g++/make — sanitizer toolchain not present")
    libtsan = _libtsan()
    if not libtsan:
        pytest.skip("g++ has no libtsan — sanitizer runtime not present")
    _ensure_tsan_build()

    env = dict(os.environ)
    # preload: the instrumented .so needs the TSAN runtime resident
    # before the (uninstrumented) python binary maps it
    env["LD_PRELOAD"] = libtsan
    # keep running past a report so every race in the run is captured;
    # exitcode=66 still fails the subprocess at exit when any fired
    env["TSAN_OPTIONS"] = "exitcode=66 halt_on_error=0"
    proc = subprocess.run(
        [sys.executable, "-c", DRIVER, str(TSAN_SO.parent)],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=str(REPO))
    report = f"rc={proc.returncode}\n{proc.stdout}\n{proc.stderr}"
    assert "WARNING: ThreadSanitizer" not in report, (
        "data race in the native shard parse/keytab path:\n" + report)
    assert proc.returncode == 0 and "TSAN_STRESS_OK" in proc.stdout, (
        "TSAN stress driver did not complete cleanly:\n" + report)
