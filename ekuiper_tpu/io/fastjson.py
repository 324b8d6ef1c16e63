"""Native columnar JSON decode — loader for native/jsoncol.cpp (ekjsoncol).

The ingest hot path hands a broker drain (list of raw JSON object payloads)
plus the stream's typed schema to the C decoder, which fills numpy columns +
validity masks in one pass (repeated strings interned). Falls back to the
Python decode+from_messages chain when the extension is unavailable, the
schema has non-scalar fields, or the C parser raises Fallback (int64
overflow, non-bytes payloads).

Reference analogue: the schema-aware fastjson converter
(/root/reference/internal/converter/json) that feeds SliceTuple columns.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

from ..data.types import DataType, Schema
from ..utils import nativebuild
from ..utils.infra import logger

_LIB = "ekjsoncol.so"
_SOURCES = ("jsoncol.cpp",)
_lock = threading.Lock()
_mod = None
_tried = False
_build_started = False

_FIELD_TYPES = {
    DataType.FLOAT: 0,
    DataType.BIGINT: 1,
    DataType.BOOLEAN: 2,
    DataType.STRING: 3,
}


def _build() -> bool:
    """False (logged there) leaves ingest on the python decode path."""
    return nativebuild.build(_LIB, _SOURCES)


def ensure_native(background: bool = True) -> bool:
    """Kick off the native build once per process when native/build holds
    no decoder built from the current sources; never blocks ingest unless
    `background` is False. Returns whether a current decoder is in place
    on return."""
    global _build_started
    with _lock:
        if nativebuild.is_current(_LIB, _SOURCES):
            return True
        if _tried or _build_started:
            return False
        _build_started = True
    if background:
        threading.Thread(target=_build, daemon=True,
                         name="ekjsoncol-build").start()
        return False
    return _build()


def _load():
    global _mod, _tried
    with _lock:
        if _tried:
            return _mod
        if not nativebuild.is_current(_LIB, _SOURCES):
            # missing, or built from other sources than the tree holds:
            # keep probing; a background build may land
            return None
        try:
            import importlib.util

            spec = importlib.util.spec_from_file_location(
                "ekjsoncol", nativebuild.lib_path(_LIB))
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            _mod = mod
        except Exception as e:
            logger.warning("ekjsoncol load failed (%s); python decode", e)
            _mod = None
        _tried = True
        return _mod


def schema_field_spec(schema: Optional[Schema]):
    """((name, ctype), ...) when every schema field is C-decodable, else
    None (caller uses the Python path)."""
    if schema is None or schema.schemaless or not schema.fields:
        return None
    spec = []
    for f in schema.fields:
        t = _FIELD_TYPES.get(f.type)
        if t is None:
            return None
        spec.append((f.name, t))
    return tuple(spec)


def native_module():
    """The loaded ekjsoncol module, or None. Does NOT trigger a build —
    callers that can start one use ensure_native(); everything else (the
    key-slot encode fast path in ops/keytable.py) just rides whatever a
    source already built."""
    return _load()


def has_keytab(api: str = "keytab_encode") -> bool:
    """True when the loaded native decoder carries the persistent key-slot
    table entry point `api` (`keytab_encode` for str keys,
    `keytab_encode_i64` for integer keys)."""
    mod = _load()
    return mod is not None and hasattr(mod, api)


def decode_columns(
    payloads: List[bytes], field_spec, shards: int = 1,
    tally: Optional[Dict[str, int]] = None,
) -> Optional[Tuple[Dict[str, Any], Dict[str, Any], Any]]:
    """(columns, valid, bad) via the native decoder, or None to fall back.
    shards > 1 splits the GIL-free parse pass across that many native
    threads (contiguous payload slices into one shared allocation) —
    output is byte-identical for any shard count. A `tally` dict is given
    the parse's own counts: `kept` / `skipped` (object members decoded
    into a column of `field_spec` / stepped over because it names no such
    column) and `bytes` (payload bytes read)."""
    mod = _load()
    if mod is None:
        return None
    try:
        cols, valid, bad, (kept, skipped, n_bytes) = mod.decode(
            list(payloads), field_spec, int(shards))
    except mod.Fallback:
        return None
    except Exception as e:
        logger.warning("ekjsoncol decode error (%s); python fallback", e)
        return None
    if tally is not None:
        tally.update(kept=kept, skipped=skipped, bytes=n_bytes)
    return cols, valid, bad
