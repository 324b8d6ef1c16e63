"""IPC socket layer for the portable-plugin boundary.

Analogue of the reference's nanomsg wrapper (pkg/nng/sock.go:37-148). Two
implementations of the same framed-transport semantics:

- native: ctypes bindings over native/ekipc.cpp (libekipc.so) — poll-based
  fan-in, 4-byte LE length framing over unix-domain or TCP sockets. Built
  on demand with `make -C native` (g++ is in the base image).
- pure-python fallback: same wire format, stdlib `socket` — used when the
  shared library can't be built (keeps tests hermetic).

Protocols (reference: connection.go:182-225 — host always LISTENS, worker
always DIALS):
  PAIR       bidirectional single peer — control + function channels
             (REQ/REP discipline is enforced by the callers)
  PUSH/PULL  one-way; PULL fans-in frames from N dialed peers
"""
from __future__ import annotations

import ctypes
import os
import socket as pysocket
import struct
import threading
import time
from typing import List, Optional, Tuple

from ..utils import nativebuild
from ..utils.infra import logger

PAIR, PUSH, PULL = 0, 1, 2

_ERR, _TIMEOUT, _CLOSED = -1, -2, -3


class IpcTimeout(Exception):
    pass


class IpcClosed(Exception):
    pass


# --------------------------------------------------------------------- native
_LIB = "libekipc.so"
_SOURCES = ("ekipc.cpp",)
_lib = None
_lib_tried = False
_lib_lock = threading.Lock()


_build_started = False


def _build_native() -> bool:
    """Compile and install libekipc.so (utils/nativebuild.py). Runs in a
    background thread via ensure_native, never on a request path. False
    (toolchain unavailable, logged there) leaves the pure-python ipc."""
    return nativebuild.build(_LIB, _SOURCES, timeout=120)


def ensure_native(background: bool = True) -> None:
    """Kick off (or finish) the native build. Called at manager/server init so
    the first plugin request never blocks on the compiler. Idempotent: only
    one build is ever started per process."""
    global _build_started
    with _lib_lock:
        if (nativebuild.is_current(_LIB, _SOURCES) or _lib_tried
                or _build_started):
            return
        _build_started = True
    if background:
        threading.Thread(target=_build_native, daemon=True,
                         name="ekipc-build").start()
    else:
        _build_native()


def _load_native() -> Optional[ctypes.CDLL]:
    global _lib, _lib_tried
    with _lib_lock:
        if _lib_tried:
            return _lib
        so = nativebuild.lib_path(_LIB)
        if not nativebuild.is_current(_LIB, _SOURCES):
            # not built from the current sources (yet): use the pure
            # fallback for now, but keep probing — a background
            # ensure_native build may finish later
            return None
        _lib_tried = True
        try:
            lib = ctypes.CDLL(so)
            lib.eks_new.restype = ctypes.c_int
            lib.eks_new.argtypes = [ctypes.c_int]
            lib.eks_listen.restype = ctypes.c_int
            lib.eks_listen.argtypes = [ctypes.c_int, ctypes.c_char_p]
            lib.eks_dial.restype = ctypes.c_int
            lib.eks_dial.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_int]
            lib.eks_send.restype = ctypes.c_int
            lib.eks_send.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_int, ctypes.c_int]
            lib.eks_recv.restype = ctypes.c_int64
            lib.eks_recv.argtypes = [ctypes.c_int, ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte)), ctypes.c_int]
            lib.eks_free_msg.argtypes = [ctypes.POINTER(ctypes.c_ubyte)]
            lib.eks_close.restype = ctypes.c_int
            lib.eks_close.argtypes = [ctypes.c_int]
            _lib = lib
        except Exception as e:
            logger.warning("ekipc load failed (%s); using pure-python ipc", e)
            _lib = None
        return _lib


class _NativeSocket:
    def __init__(self, proto: int) -> None:
        self._lib = _load_native()
        assert self._lib is not None
        self._h = self._lib.eks_new(proto)
        if self._h < 0:
            raise OSError("eks_new failed")

    def listen(self, url: str) -> None:
        if self._lib.eks_listen(self._h, url.encode()) != 0:
            raise OSError(f"listen {url} failed")

    def dial(self, url: str, timeout_ms: int = 5000) -> None:
        rc = self._lib.eks_dial(self._h, url.encode(), timeout_ms)
        if rc == _TIMEOUT:
            raise IpcTimeout(f"dial {url}")
        if rc != 0:
            raise OSError(f"dial {url} failed ({rc})")

    def send(self, data: bytes, timeout_ms: int = -1) -> None:
        rc = self._lib.eks_send(self._h, data, len(data), timeout_ms)
        if rc == _TIMEOUT:
            raise IpcTimeout("send")
        if rc == _CLOSED:
            raise IpcClosed("send")
        if rc != 0:
            raise OSError(f"send failed ({rc})")

    def recv(self, timeout_ms: int = -1) -> bytes:
        out = ctypes.POINTER(ctypes.c_ubyte)()
        n = self._lib.eks_recv(self._h, ctypes.byref(out), timeout_ms)
        if n == _TIMEOUT:
            raise IpcTimeout("recv")
        if n == _CLOSED:
            raise IpcClosed("recv")
        if n < 0:
            raise OSError(f"recv failed ({n})")
        try:
            return bytes(ctypes.cast(out, ctypes.POINTER(ctypes.c_ubyte * n)).contents) if n else b""
        finally:
            self._lib.eks_free_msg(out)

    def close(self) -> None:
        self._lib.eks_close(self._h)


# -------------------------------------------------------------- pure fallback
def _parse_url(url: str):
    if url.startswith("ipc://"):
        return ("unix", url[6:])
    if url.startswith("tcp://"):
        host, _, port = url[6:].rpartition(":")
        return ("tcp", (host, int(port)))
    raise ValueError(f"bad url {url}")


class _PySocket:
    """Stdlib implementation of the same semantics (fan-in PULL, PAIR)."""

    def __init__(self, proto: int) -> None:
        self.proto = proto
        self._listener: Optional[pysocket.socket] = None
        self._conns: List[Tuple[pysocket.socket, bytearray]] = []
        self._mu = threading.Lock()
        self._unlink: Optional[str] = None
        self._closed = False

    def listen(self, url: str) -> None:
        kind, addr = _parse_url(url)
        if kind == "unix":
            try:
                os.unlink(addr)
            except OSError:
                pass
            s = pysocket.socket(pysocket.AF_UNIX, pysocket.SOCK_STREAM)
            s.bind(addr)
            self._unlink = addr
        else:
            s = pysocket.socket(pysocket.AF_INET, pysocket.SOCK_STREAM)
            s.setsockopt(pysocket.SOL_SOCKET, pysocket.SO_REUSEADDR, 1)
            s.bind(addr)
        s.listen(64)
        s.settimeout(0.05)
        self._listener = s

    def dial(self, url: str, timeout_ms: int = 5000) -> None:
        kind, addr = _parse_url(url)
        deadline = time.monotonic() + timeout_ms / 1000.0
        while True:
            try:
                fam = pysocket.AF_UNIX if kind == "unix" else pysocket.AF_INET
                s = pysocket.socket(fam, pysocket.SOCK_STREAM)
                s.connect(addr)
                s.settimeout(0.05)
                with self._mu:
                    self._conns.append((s, bytearray()))
                return
            except OSError:
                try:
                    s.close()
                except OSError:
                    pass
                if time.monotonic() >= deadline:
                    raise IpcTimeout(f"dial {url}")
                time.sleep(0.02)

    def _accept(self) -> None:
        if self._listener is None:
            return
        while True:
            try:
                c, _ = self._listener.accept()
                c.settimeout(0.05)
                with self._mu:
                    self._conns.append((c, bytearray()))
            except (pysocket.timeout, OSError):
                return

    def send(self, data: bytes, timeout_ms: int = -1) -> None:
        deadline = None if timeout_ms < 0 else time.monotonic() + timeout_ms / 1000.0
        while True:
            if self._closed:
                raise IpcClosed("send")
            self._accept()
            with self._mu:
                conn = self._conns[-1][0] if self._conns else None
            if conn is not None:
                break
            if deadline is not None and time.monotonic() >= deadline:
                raise IpcTimeout("send")
            time.sleep(0.01)
        frame = struct.pack("<I", len(data)) + data
        try:
            conn.sendall(frame)
        except OSError:
            raise IpcClosed("send")

    def recv(self, timeout_ms: int = -1) -> bytes:
        deadline = None if timeout_ms < 0 else time.monotonic() + timeout_ms / 1000.0
        while True:
            if self._closed:
                raise IpcClosed("recv")
            self._accept()
            with self._mu:
                conns = list(self._conns)
            for s, buf in conns:
                # complete frame already buffered?
                if len(buf) >= 4:
                    (ln,) = struct.unpack("<I", buf[:4])
                    if len(buf) >= 4 + ln:
                        payload = bytes(buf[4:4 + ln])
                        del buf[:4 + ln]
                        return payload
                try:
                    chunk = s.recv(65536)
                    if chunk:
                        buf.extend(chunk)
                        continue
                    # EOF
                    with self._mu:
                        self._conns = [(c, b) for c, b in self._conns if c is not s]
                    s.close()
                    if self.proto == PAIR and self._listener is None and not self._conns:
                        raise IpcClosed("recv")
                except pysocket.timeout:
                    pass
                except IpcClosed:
                    raise
                except OSError:
                    with self._mu:
                        self._conns = [(c, b) for c, b in self._conns if c is not s]
            if deadline is not None and time.monotonic() >= deadline:
                raise IpcTimeout("recv")

    def close(self) -> None:
        self._closed = True
        if self._listener is not None:
            self._listener.close()
        with self._mu:
            for s, _ in self._conns:
                try:
                    s.close()
                except OSError:
                    pass
            self._conns.clear()
        if self._unlink:
            try:
                os.unlink(self._unlink)
            except OSError:
                pass


# ------------------------------------------------------------------- factory
_FORCE_PURE = os.environ.get("EKUIPER_TPU_PURE_IPC") == "1"


def Socket(proto: int):
    """Create a PAIR/PUSH/PULL socket, preferring the native transport."""
    if not _FORCE_PURE and _load_native() is not None:
        return _NativeSocket(proto)
    return _PySocket(proto)


# Per-engine namespace token embedded in every ipc path so two engine
# instances (or parallel test runs) on one machine can't steal each other's
# endpoints. Worker processes inherit it through the environment, so both
# ends of a channel derive identical urls.
_IPC_NS = os.environ.setdefault("EKUIPER_TPU_IPC_NS", str(os.getpid()))


def _ipc_dir() -> str:
    """Mode-0700 per-instance runtime dir: unix sockets under it are only
    dialable by the engine's own uid (unlike the reference's world-readable
    ipc:///tmp/plugin_*.ipc endpoints)."""
    base = os.environ.get("EKUIPER_TPU_RUNTIME_DIR") or os.path.join(
        "/tmp", f"ektpu_{_IPC_NS}")
    os.makedirs(base, mode=0o700, exist_ok=True)
    # A pre-created/symlinked dir (pids are predictable) would hand the
    # endpoint to an attacker — verify rather than trust: must be a real
    # directory, owned by us, no group/other access.
    st = os.lstat(base)
    import stat as _stat
    if not _stat.S_ISDIR(st.st_mode):
        raise RuntimeError(f"ipc runtime dir {base} is not a directory")
    if st.st_uid != os.getuid():
        raise RuntimeError(f"ipc runtime dir {base} owned by uid {st.st_uid}")
    if st.st_mode & 0o077:
        os.chmod(base, 0o700)  # raises on failure — do not fall through
    return base


def ipc_url(name: str) -> str:
    """ipc://{runtime_dir}/{name}.ipc — reference url scheme (connection.go:56)
    with a per-instance 0700 directory instead of bare /tmp."""
    return f"ipc://{os.path.join(_ipc_dir(), name + '.ipc')}"
