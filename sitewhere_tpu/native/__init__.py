"""ctypes bindings for the native host runtime (host_runtime.cc).

The shared library is compiled on first import with the toolchain g++ (no
external build system, no pybind11 — plain `extern "C"` + ctypes) and cached
next to the source; a stale cache (source newer than .so) rebuilds. Import
never fails: if the compiler or the build is unavailable the module exposes
``LIB = None`` and callers fall back to their pure-Python paths.

Set SITEWHERE_TPU_NO_NATIVE=1 to force the fallback (used by tests to cover
both paths).
"""

from __future__ import annotations

import ctypes
import os
import subprocess
from typing import List, Optional, Tuple

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "host_runtime.cc")
_SO = os.path.join(_DIR, "libswt_host.so")

LIB: Optional[ctypes.CDLL] = None
_build_error: Optional[str] = None
_compiled = False  # this process built the library from host_runtime.cc


def _build() -> Optional[str]:
    """Compile the shared library if missing/stale; returns error or None."""
    global _compiled
    try:
        if (os.path.exists(_SO)
                and os.path.getmtime(_SO) >= os.path.getmtime(_SRC)):
            return None
        tmp = f"{_SO}.{os.getpid()}.tmp"  # unique per process: concurrent
        cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC",  # first imports
               "-o", tmp, _SRC]                # must not interleave writes
        proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
        if proc.returncode != 0:
            return proc.stderr[-2000:]
        os.replace(tmp, _SO)
        _compiled = True
        return None
    except (OSError, subprocess.SubprocessError) as exc:
        return str(exc)


def _load(_retry: bool = True) -> None:
    global LIB, _build_error
    if os.environ.get("SITEWHERE_TPU_NO_NATIVE") == "1":
        _build_error = "disabled by SITEWHERE_TPU_NO_NATIVE"
        return
    _build_error = _build()
    if _build_error is not None:
        return
    try:
        lib = ctypes.CDLL(_SO)
    except OSError as exc:
        _build_error = str(exc)
        return
    c = ctypes
    i32, i64, vp = c.c_int32, c.c_int64, c.c_void_p
    p_i32 = np.ctypeslib.ndpointer(np.int32, flags="C_CONTIGUOUS")
    p_i64 = np.ctypeslib.ndpointer(np.int64, flags="C_CONTIGUOUS")
    p_f32 = np.ctypeslib.ndpointer(np.float32, flags="C_CONTIGUOUS")
    # ABI gate FIRST: a stale cached .so (mtime-preserving deploys defeat
    # the staleness check) must not crash the import when a newer binding
    # looks up a symbol the old library doesn't export. The condition the
    # gate detects is also repairable: delete the stale cache and rebuild
    # from source once.
    try:
        lib.swt_version.restype = i32
        stale = lib.swt_version() != 9
    except AttributeError:
        stale = True
    if stale:
        if _retry:
            try:
                # dlopen dedupes by pathname: the stale mapping must be
                # dlclose'd or the rebuilt library would never be loaded
                import _ctypes

                _ctypes.dlclose(lib._handle)
                # missing_ok: a concurrent process may have repaired the
                # cache already — that's success, proceed to reload
                try:
                    os.remove(_SO)
                except FileNotFoundError:
                    pass
            except OSError as exc:
                _build_error = f"stale libswt_host.so (unremovable: {exc})"
                return
            _load(_retry=False)
            return
        _build_error = "version mismatch persists after rebuild"
        return
    lib.swt_interner_create.argtypes = [i32]
    lib.swt_interner_create.restype = vp
    lib.swt_interner_destroy.argtypes = [vp]
    lib.swt_interner_size.argtypes = [vp]
    lib.swt_interner_size.restype = i32
    lib.swt_interner_add.argtypes = [vp, c.c_char_p, i32]
    lib.swt_interner_add.restype = i32
    lib.swt_interner_add_gap.argtypes = [vp]
    lib.swt_interner_add_gap.restype = i32
    lib.swt_interner_token_at.argtypes = [vp, i32, c.c_char_p, i32]
    lib.swt_interner_token_at.restype = i32
    lib.swt_interner_set_at.argtypes = [vp, i32, c.c_char_p, i32]
    lib.swt_interner_set_at.restype = i32
    lib.swt_interner_lookup_offsets.argtypes = [vp, c.c_char_p, p_i64, i32,
                                                p_i32]
    lib.swt_interner_lookup_offsets.restype = i32
    lib.swt_interner_intern_offsets.argtypes = [vp, c.c_char_p, p_i64, i32,
                                                p_i32, i32]
    lib.swt_interner_intern_offsets.restype = i32
    lib.swt_decode_hot_frames.argtypes = [
        c.c_char_p, i64, i32,
        p_i32, p_i64, p_f32, p_f32, p_f32, p_f32, p_i32,
        c.c_char_p, i64, p_i64,
        c.c_char_p, i64, p_i64,
        c.c_char_p, i64, p_i64,
        p_i32, p_i64, p_i64, i32, p_i64]
    lib.swt_decode_hot_frames.restype = i32
    lib.swt_route_blob.argtypes = [p_i32, i64, i32, i32, i32, p_i32, p_i64,
                                   i64]
    lib.swt_route_blob.restype = i32
    p_u8 = np.ctypeslib.ndpointer(np.uint8, flags="C_CONTIGUOUS")
    lib.swt_pack_route_blob.argtypes = [p_i32, p_i32, p_i32, p_i32, p_f32,
                                        p_f32, p_f32, p_f32, p_i32, p_i32,
                                        p_u8, i64, i32, i32, i32, i32,
                                        p_i32, p_i64, i64]
    lib.swt_pack_route_blob.restype = i32
    lib.swt_pack_blob.argtypes = [p_i32, p_i32, p_i32, p_i32, p_f32, p_f32,
                                  p_f32, p_f32, p_i32, p_i32, p_u8, i64,
                                  i32, i32, p_i32]
    lib.swt_pack_blob.restype = i32
    lib.swt_unpack_blob.argtypes = [p_i32, i64, i32, p_i32, p_i32, p_i32,
                                    p_i32, p_f32, p_f32, p_f32, p_f32, p_i32,
                                    p_i32, p_u8]
    lib.swt_unpack_blob.restype = None
    LIB = lib


_load()


def available() -> bool:
    return LIB is not None


def build_error() -> Optional[str]:
    return _build_error


def load_report() -> str:
    """One line for operators: whether the library in use was loaded as
    found or built here from host_runtime.cc, or why it is unavailable."""
    if LIB is None:
        return f"fallback ({_build_error})"
    how = "built here from host_runtime.cc" if _compiled else "loaded as found"
    return f"ok ({how}: {_SO})"


def join_tokens(tokens) -> Tuple[bytes, np.ndarray]:
    """Encode a sequence of str/bytes tokens into (joined buffer, offsets)."""
    enc = [t.encode(errors="surrogateescape") if isinstance(t, str) else t
           for t in tokens]
    off = np.zeros(len(enc) + 1, np.int64)
    np.cumsum([len(t) for t in enc], out=off[1:])
    return b"".join(enc), off


class NativeInterner:
    """Thin RAII wrapper over swt_interner_* (index 0 = UNKNOWN)."""

    def __init__(self, capacity: int):
        assert LIB is not None
        self._h = LIB.swt_interner_create(capacity)
        if not self._h:
            raise MemoryError("swt_interner_create failed")

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h and LIB is not None:
            LIB.swt_interner_destroy(h)

    def __len__(self) -> int:
        return LIB.swt_interner_size(self._h)

    def add(self, token: str) -> int:
        """Get-or-assign; -1 signals capacity exceeded."""
        raw = token.encode(errors="surrogateescape")
        return LIB.swt_interner_add(self._h, raw, len(raw))

    def add_gap(self) -> int:
        """Append an unfindable gap-placeholder slot (the shard-congruent
        allocator); returns its index, -1 on capacity exceeded."""
        return LIB.swt_interner_add_gap(self._h)

    def set_at(self, idx: int, token: str) -> int:
        """Overwrite a gap-placeholder slot with a real token (the
        shard-congruent allocator). 0 ok, -1 bad index, -2 token exists
        at a different index."""
        raw = token.encode(errors="surrogateescape")
        return LIB.swt_interner_set_at(self._h, idx, raw, len(raw))

    def token_at(self, idx: int) -> Optional[str]:
        cap = 1024
        while True:
            buf = ctypes.create_string_buffer(cap)
            n = LIB.swt_interner_token_at(self._h, idx, buf, cap)
            if n >= 0:
                # tokens are raw wire bytes; surrogateescape keeps non-UTF-8
                # byte sequences round-trippable through the str mirror
                return buf.raw[:n].decode(errors="surrogateescape")
            if n == -1:
                return None
            cap = -n - 2  # buffer was too small; retry at the exact size

    def lookup_offsets(self, buf: bytes, off: np.ndarray) -> np.ndarray:
        n = len(off) - 1
        out = np.empty(n, np.int32)
        LIB.swt_interner_lookup_offsets(self._h, buf, off, n, out)
        return out

    def intern_offsets(self, buf: bytes, off: np.ndarray,
                       skip_empty: bool = False) -> Tuple[np.ndarray, bool]:
        """Returns (indices, capacity_ok). skip_empty maps zero-length
        tokens to UNKNOWN without interning them."""
        n = len(off) - 1
        out = np.empty(n, np.int32)
        rc = LIB.swt_interner_intern_offsets(self._h, buf, off, n, out,
                                             1 if skip_empty else 0)
        return out, rc == 0

    def lookup_batch(self, tokens) -> np.ndarray:
        buf, off = join_tokens(tokens)
        return self.lookup_offsets(buf, off)

    def intern_batch(self, tokens) -> Tuple[np.ndarray, bool]:
        buf, off = join_tokens(tokens)
        return self.intern_offsets(buf, off)


class DecodedColumns:
    """Output of decode_hot_frames: SoA columns + string buffers + control
    frames. String columns stay as (bytes, offsets) so they can feed the
    native interner without materializing Python strings."""

    __slots__ = ("n", "event_type", "ts_ms", "value", "lat", "lon",
                 "elevation", "alert_level", "tokens", "names", "alert_types",
                 "others", "consumed")

    def __init__(self, n, event_type, ts_ms, value, lat, lon, elevation,
                 alert_level, tokens, names, alert_types, others, consumed):
        self.n = n
        self.event_type = event_type
        self.ts_ms = ts_ms
        self.value = value
        self.lat = lat
        self.lon = lon
        self.elevation = elevation
        self.alert_level = alert_level
        self.tokens = tokens            # (bytes, offsets[n+1])
        self.names = names              # (bytes, offsets[n+1])
        self.alert_types = alert_types  # (bytes, offsets[n+1])
        self.others = others            # [(msg_type, payload bytes)]
        self.consumed = consumed

    def token_list(self) -> List[str]:
        buf, off = self.tokens
        return [buf[off[i]:off[i + 1]].decode(errors="surrogateescape")
                for i in range(self.n)]


from sitewhere_tpu.transport.wire import WireError as _WireError


class WireDecodeError(_WireError):
    """Raised on malformed wire streams; subclasses transport.wire.WireError
    so `except WireError` handlers cover both ingest lanes."""


def decode_hot_frames(data: bytes, max_events: Optional[int] = None
                      ) -> DecodedColumns:
    """Single-pass native decode of a wire byte stream (see host_runtime.cc).

    Raises WireDecodeError on malformed input; a trailing partial frame is
    returned via `consumed` (callers keep the remainder buffered).
    """
    assert LIB is not None
    cap = max_events if max_events is not None else max(len(data) // 13, 1)
    et = np.empty(cap, np.int32)
    ts = np.empty(cap, np.int64)
    val = np.empty(cap, np.float32)
    lat = np.empty(cap, np.float32)
    lon = np.empty(cap, np.float32)
    ele = np.empty(cap, np.float32)
    lvl = np.empty(cap, np.int32)
    tok_cap = len(data)
    tok_buf = ctypes.create_string_buffer(tok_cap or 1)
    name_buf = ctypes.create_string_buffer(tok_cap or 1)
    atype_buf = ctypes.create_string_buffer(tok_cap or 1)
    tok_off = np.zeros(cap + 1, np.int64)
    name_off = np.zeros(cap + 1, np.int64)
    atype_off = np.zeros(cap + 1, np.int64)
    other_cap = max(len(data) // 8, 1)
    other_type = np.empty(other_cap, np.int32)
    other_off = np.empty(other_cap, np.int64)
    other_len = np.empty(other_cap, np.int64)
    counts = np.zeros(4, np.int64)
    LIB.swt_decode_hot_frames(
        data, len(data), cap, et, ts, val, lat, lon, ele, lvl,
        tok_buf, tok_cap, tok_off, name_buf, tok_cap, name_off,
        atype_buf, tok_cap, atype_off,
        other_type, other_off, other_len, other_cap, counts)
    n, m, consumed, err = (int(counts[0]), int(counts[1]), int(counts[2]),
                           int(counts[3]))
    if err == 1:
        raise WireDecodeError("bad magic/version")
    if err == 3:
        raise WireDecodeError("malformed frame payload")
    if err == 2:
        raise WireDecodeError("decode capacity exceeded")
    others = [(int(other_type[i]),
               data[int(other_off[i]):int(other_off[i]) + int(other_len[i])])
              for i in range(m)]
    return DecodedColumns(
        n, et[:n], ts[:n], val[:n], lat[:n], lon[:n], ele[:n], lvl[:n],
        (tok_buf.raw[:int(tok_off[n])], tok_off[:n + 1]),
        (name_buf.raw[:int(name_off[n])], name_off[:n + 1]),
        (atype_buf.raw[:int(atype_off[n])], atype_off[:n + 1]),
        others, consumed)


def route_blob(blob: np.ndarray, n_shards: int, per_shard: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """Shard-route a flat wire blob [wire_rows, n] -> ([S, wire_rows, B]
    routed blob, flat-row indices of overflow); wire_rows follows the
    input blob (4 = compact). Requires available(); callers fall back to
    the numpy router otherwise."""
    blob = np.ascontiguousarray(blob, np.int32)
    rows, n = blob.shape
    out = np.zeros((n_shards, rows, per_shard), np.int32)
    overflow = np.empty(max(n, 1), np.int64)
    n_over = LIB.swt_route_blob(blob.reshape(-1), n, n_shards, per_shard,
                                rows, out.reshape(-1), overflow,
                                len(overflow))
    if n_over < 0:  # cannot happen with overflow_cap=n; defensive
        raise RuntimeError("route_blob overflow capacity exceeded")
    return out, overflow[:n_over]


def pack_route_blob(batch, n_shards: int, per_shard: int,
                    out: Optional[np.ndarray] = None,
                    wire_rows: Optional[int] = None,
                    ts_base: int = 0
                    ) -> Optional[Tuple[np.ndarray, np.ndarray]]:
    """Fused pack+route: EventBatch columns -> routed [S, wire_rows, B]
    blob + overflow flat-row indices in ONE native pass (see
    swt_pack_route_blob). wire_rows 5, or 4 for the compact no-elevation
    variant; derived from `out` when a buffer is supplied. `out` may be a
    reused staging buffer — it does NOT need to be zeroed (the kernel
    clears exactly the head-row tails whose valid bits must read 0).
    Returns None when a device_idx is out of wire range (caller raises
    the shared diagnostic). Requires available()."""
    from sitewhere_tpu.ops.pack import WIRE_ROWS

    n = batch.device_idx.shape[0]
    if out is not None:
        wire_rows = out.shape[1]
    elif wire_rows is None:
        wire_rows = WIRE_ROWS
    if out is None:
        out = np.empty((n_shards, wire_rows, per_shard), np.int32)

    def i32(a):
        return np.ascontiguousarray(a, np.int32)

    def f32(a):
        return np.ascontiguousarray(a, np.float32)

    overflow = np.empty(max(n, 1), np.int64)
    rc = LIB.swt_pack_route_blob(
        i32(batch.device_idx), i32(batch.event_type), i32(batch.ts),
        i32(batch.mm_idx), f32(batch.value), f32(batch.lat), f32(batch.lon),
        f32(batch.elevation), i32(batch.alert_type_idx),
        i32(batch.alert_level),
        np.ascontiguousarray(batch.valid, np.uint8), n, n_shards, per_shard,
        wire_rows, ts_base, out.reshape(-1), overflow, len(overflow))
    if rc == -2:
        return None
    if rc < 0:  # cannot happen with overflow_cap=n; defensive
        raise RuntimeError("pack_route_blob overflow capacity exceeded")
    return out, overflow[:rc]


def pack_blob(batch, out: np.ndarray, ts_base: int = 0) -> bool:
    """One-pass EventBatch columns -> [wire_rows, n] wire blob (flat
    batches only; leading-axis batches use the numpy path; wire_rows from
    out.shape[0] — 4 = compact no-elevation variant). Returns False when
    a device_idx is out of wire range (caller raises with detail).
    Requires available()."""
    n = batch.device_idx.shape[0]

    def i32(a):
        return np.ascontiguousarray(a, np.int32)

    def f32(a):
        return np.ascontiguousarray(a, np.float32)

    rc = LIB.swt_pack_blob(
        i32(batch.device_idx), i32(batch.event_type), i32(batch.ts),
        i32(batch.mm_idx), f32(batch.value), f32(batch.lat), f32(batch.lon),
        f32(batch.elevation), i32(batch.alert_type_idx),
        i32(batch.alert_level),
        np.ascontiguousarray(batch.valid, np.uint8), n, out.shape[0],
        ts_base, out.reshape(-1))
    return rc == 0


def unpack_blob(blob: np.ndarray, cols: dict) -> None:
    """One-pass [wire_rows, n] wire blob -> preallocated column arrays
    (keys: device_idx..valid; 4-row compact blobs unpack with elevation
    0). Requires available()."""
    n = blob.shape[-1]
    LIB.swt_unpack_blob(
        np.ascontiguousarray(blob, np.int32).reshape(-1), n, blob.shape[-2],
        cols["device_idx"], cols["event_type"], cols["ts"], cols["mm_idx"],
        cols["value"], cols["lat"], cols["lon"], cols["elevation"],
        cols["alert_type_idx"], cols["alert_level"], cols["valid"])
