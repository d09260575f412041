"""ctypes bindings for the C++ host runtime (cpp/arroyo_host.cc).

The library is built on first use with `make -C cpp` (g++ is in the image)
under a file name that carries a hash of the sources it was built from
(``libarroyo_host-<key>.so``), so a library built from other sources is
never loaded and a matching one is never rebuilt — file times play no part.

Every entry point has a NumPy fallback: ``lib()`` returns None (logging the
reason once) when the library cannot be built or loaded, and the config
flag ``native.enabled`` force-disables the native path. A caller for whom
that fallback would hide a slower host path calls ``require()`` instead,
which raises with the reason.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import logging
import os
import subprocess
import threading
from typing import Optional

import numpy as np

logger = logging.getLogger(__name__)

_CPP_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "cpp")
# everything that decides what the built library contains
_KEY_SOURCES = ("arroyo_host.cc", "Makefile")

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_lib_kept: Optional[ctypes.PyDLL] = None  # the same library, called with the interpreter lock kept
_DIR_MAX_BINS = 0  # ah_dir_max_bins(): the distinct bins one claim takes
_STEP_MAX_BINS = 0  # ah_step_max_bins(): the distinct bins one made step spans
_lib_failed = False
_lib_error: Optional[str] = None  # why _lib_failed, for require()


class NativeUnavailable(RuntimeError):
    """The host library could not be built or loaded (message says why)."""


def lib_path() -> str:
    """Where the library built from the sources now in ``cpp/`` lives."""
    h = hashlib.sha256()
    for name in _KEY_SOURCES:
        with open(os.path.join(_CPP_DIR, name), "rb") as f:
            h.update(f.read())
    return os.path.join(_CPP_DIR, f"libarroyo_host-{h.hexdigest()[:16]}.so")


def _build(path: str) -> None:
    # build beside the target and rename: a concurrent loader (another
    # worker process) never maps a half-written file
    tmp = f"{path}.{os.getpid()}.tmp"
    try:
        r = subprocess.run(
            ["make", "-C", _CPP_DIR, f"TARGET={os.path.basename(tmp)}"],
            capture_output=True, text=True, timeout=120,
        )
    except (OSError, subprocess.TimeoutExpired) as e:
        raise NativeUnavailable(f"make -C {_CPP_DIR} did not run: {e}") from e
    if r.returncode != 0 or not os.path.exists(tmp):
        raise NativeUnavailable(
            f"make -C {_CPP_DIR} failed (rc {r.returncode}):\n{r.stderr[-2000:]}")
    os.replace(tmp, path)
    for old in glob.glob(os.path.join(_CPP_DIR, "libarroyo_host*.so")):
        if old != path:
            os.remove(old)


def _load() -> tuple:
    try:
        path = lib_path()
    except OSError as e:
        raise NativeUnavailable(f"sources not readable: {e}") from e
    if not os.path.exists(path):
        _build(path)
    try:
        # ctypes.CDLL lets go of the interpreter lock round every call. The
        # slot directory's two (dir_resolve, dir_claim) were measured on the
        # chip against ctypes.PyDLL, which keeps it: letting go won, the
        # probe's half millisecond a step runs beside the other tasks'
        # Python (PERF.md section 6, PR 48)
        l = ctypes.CDLL(path)
        _declare(l)
        # ah_step_make is called through ctypes.PyDLL, which keeps the lock:
        # the pass over a step's rows is tens of microseconds, and measured
        # on the chip against CDLL in q5-hour-sat and top5-sat keeping it
        # won two of three pairs and lost nothing (PERF.md section 6, PR 53)
        kept = ctypes.PyDLL(path)
        _declare_step_make(kept)
    except (OSError, AttributeError) as e:
        # AttributeError: a symbol the bindings declare is missing
        raise NativeUnavailable(f"{path} does not load: {e}") from e
    return l, kept


def lib() -> Optional[ctypes.CDLL]:
    """The loaded library, building it if needed; None when unavailable."""
    global _lib, _lib_kept, _DIR_MAX_BINS, _STEP_MAX_BINS, _lib_failed, _lib_error
    if _lib is not None or _lib_failed:
        return _lib
    with _lock:
        if _lib is not None or _lib_failed:
            return _lib
        from ..config import config

        if not config().get("native.enabled", True):
            _lib_failed = True
            _lib_error = "disabled by native.enabled=false"
            return None
        try:
            l, _lib_kept = _load()
            _DIR_MAX_BINS = int(l.ah_dir_max_bins())
            _STEP_MAX_BINS = int(l.ah_step_max_bins())
            _lib = l
        except NativeUnavailable as e:
            _lib_failed = True
            _lib_error = str(e)
            logger.warning("native host library unavailable, using the NumPy "
                           "fallbacks: %s", e)
        return _lib


def require() -> ctypes.CDLL:
    """The loaded library, or NativeUnavailable saying why there is none."""
    l = lib()
    if l is None:
        raise NativeUnavailable(_lib_error or "native library unavailable")
    return l


def _declare(l: ctypes.CDLL) -> None:
    u64p = ctypes.POINTER(ctypes.c_uint64)
    i64p = ctypes.POINTER(ctypes.c_int64)
    f64p = ctypes.POINTER(ctypes.c_double)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    l.ah_hash_u64.argtypes = [u64p, u64p, ctypes.c_int64]
    l.ah_hash_combine.argtypes = [u64p, u64p, ctypes.c_int64]
    l.ah_hash_f64.argtypes = [f64p, u64p, ctypes.c_int64]
    l.ah_partition.argtypes = [u64p, ctypes.c_int64, ctypes.c_int32, i64p, i64p]
    l.ah_partition.restype = ctypes.c_int
    l.ah_dir_max_bins.argtypes = []
    l.ah_dir_max_bins.restype = ctypes.c_int64
    l.ah_dir_resolve.argtypes = [
        i64p, ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64,  # keys, bins, bins_narrow, n
        u64p, i64p, i64p,                    # hcode, hbin, hslot
        ctypes.c_int64, ctypes.c_int64,      # hcap, boundary
        i64p, i64p,                          # slot_keys, slot_bins
        ctypes.c_void_p, ctypes.c_int32, ctypes.c_int64, ctypes.c_int64,  # out_slots, slots_narrow, room, pad
        i64p,                                # miss_ord
        u64p, i64p, i64p,                    # miss_codes, miss_keys, miss_bins
        i64p, i64p, i64p,                    # miss_bin_vals, miss_bin_counts, n_miss_bins
    ]
    l.ah_dir_resolve.restype = ctypes.c_int64
    l.ah_dir_claim.argtypes = [
        u64p, i64p, i64p, ctypes.c_int64,    # miss_codes, miss_keys, miss_bins, m
        u64p, i64p, i64p,                    # hcode, hbin, hslot
        ctypes.c_int64, ctypes.c_int64,      # hcap, boundary
        i64p, i64p,                          # slot_keys, slot_bins
        i64p, ctypes.c_int64,                # ranges, n_ranges
        i64p, ctypes.c_void_p, ctypes.c_int32,  # miss_slots, out_slots, slots_narrow
        i64p, ctypes.c_int64,                # miss_ord, n
    ]
    l.ah_dir_claim.restype = ctypes.c_int64
    l.ah_pane_slide.argtypes = [
        i64p, ctypes.c_int64, ctypes.c_int64,       # state, s_stride, n
        i64p, ctypes.c_void_p, ctypes.c_int64,      # a_keys, a_lanes, a
        i64p, ctypes.c_void_p, ctypes.c_int64,      # r_keys, r_lanes, r
        ctypes.c_int32, ctypes.c_int32,             # n_lanes, n_added
        i64p, ctypes.c_int64,                       # out, o_stride
    ]
    l.ah_pane_slide.restype = ctypes.c_int64
    i32p = ctypes.POINTER(ctypes.c_int32)
    l.ah_bin_combine.argtypes = [
        i64p, ctypes.c_int64, ctypes.c_int64,       # ts, n, bin_micros
        ctypes.c_int32, i32p, i32p, ctypes.c_void_p,  # n_lanes, kinds, widths, lanes
        i64p, ctypes.c_int64,                       # out, max_bins
    ]
    l.ah_bin_combine.restype = ctypes.c_int64
    l.ah_step_max_bins.argtypes = []
    l.ah_step_max_bins.restype = ctypes.c_int64
    _declare_step_make(l)
    l.ah_parse_json_lines.argtypes = [
        ctypes.c_char_p, ctypes.c_int64, ctypes.c_int32, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int32), ctypes.c_int64,
        ctypes.POINTER(i64p), ctypes.POINTER(f64p), ctypes.POINTER(u8p),
        ctypes.POINTER(i64p), ctypes.POINTER(ctypes.c_char_p),
        ctypes.POINTER(ctypes.c_int64),
    ]
    l.ah_parse_json_lines.restype = ctypes.c_int64
    l.ah_free.argtypes = [ctypes.c_void_p]
    l.dp_listen.argtypes = [ctypes.c_char_p, ctypes.c_int]
    l.dp_listen.restype = ctypes.c_int
    l.dp_bound_port.argtypes = [ctypes.c_int]
    l.dp_bound_port.restype = ctypes.c_int
    l.dp_accept.argtypes = [ctypes.c_int]
    l.dp_accept.restype = ctypes.c_int
    l.dp_connect.argtypes = [ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_int]
    l.dp_connect.restype = ctypes.c_int
    l.dp_send_frame.argtypes = [
        ctypes.c_int, ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint32,
        ctypes.c_uint32, ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32,
    ]
    l.dp_send_frame.restype = ctypes.c_int
    l.dp_recv_header.argtypes = [ctypes.c_int, ctypes.POINTER(ctypes.c_uint32)]
    l.dp_recv_header.restype = ctypes.c_int
    l.dp_recv_payload.argtypes = [ctypes.c_int, ctypes.c_char_p, ctypes.c_uint32]
    l.dp_recv_payload.restype = ctypes.c_int
    l.dp_close.argtypes = [ctypes.c_int]


def _declare_step_make(l) -> None:
    i64p = ctypes.POINTER(ctypes.c_int64)
    i32p = ctypes.POINTER(ctypes.c_int32)
    f64p = ctypes.POINTER(ctypes.c_double)
    l.ah_step_make.argtypes = [
        ctypes.c_int64, i64p, ctypes.c_void_p, ctypes.c_void_p,  # n_pieces, rows, ts, keys
        ctypes.c_int32, i32p, i32p, ctypes.c_void_p,  # n_lanes, src, dst, cols
        ctypes.c_int64, ctypes.c_int32, ctypes.c_int32, ctypes.c_int64,  # bin_micros, anchored, has_late, late_before
        ctypes.c_int64, i64p, i32p, ctypes.c_void_p,  # room, out_keys, out_rel, out_lanes
        i64p, f64p, i64p,                             # ident_i, ident_f, info
    ]
    l.ah_step_make.restype = ctypes.c_int64


def available() -> bool:
    return lib() is not None


# --------------------------------------------------------------- hashing


def _u64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))


def hash_u64(arr: np.ndarray) -> Optional[np.ndarray]:
    l = lib()
    if l is None:
        return None
    arr = np.ascontiguousarray(arr, dtype=np.uint64)
    out = np.empty(len(arr), dtype=np.uint64)
    l.ah_hash_u64(_u64p(arr), _u64p(out), len(arr))
    return out


def hash_f64(arr: np.ndarray) -> Optional[np.ndarray]:
    l = lib()
    if l is None:
        return None
    arr = np.ascontiguousarray(arr, dtype=np.float64)
    out = np.empty(len(arr), dtype=np.uint64)
    l.ah_hash_f64(arr.ctypes.data_as(ctypes.POINTER(ctypes.c_double)), _u64p(out), len(arr))
    return out


def hash_combine(h: np.ndarray, h2: np.ndarray) -> Optional[np.ndarray]:
    l = lib()
    if l is None:
        return None
    h = np.ascontiguousarray(h, dtype=np.uint64).copy()
    h2 = np.ascontiguousarray(h2, dtype=np.uint64)
    l.ah_hash_combine(_u64p(h), _u64p(h2), len(h))
    return h


def partition(hashes: np.ndarray, n_dest: int):
    """(perm, offsets): stable grouping of row indices by destination
    (native counting sort; None if the library is unavailable)."""
    l = lib()
    if l is None:
        return None
    hashes = np.ascontiguousarray(hashes, dtype=np.uint64)
    perm = np.empty(len(hashes), dtype=np.int64)
    offsets = np.empty(n_dest + 1, dtype=np.int64)
    rc = l.ah_partition(
        _u64p(hashes), len(hashes), n_dest,
        perm.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
        offsets.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
    )
    if rc != 0:
        return None
    return perm, offsets


def _i64p(a: np.ndarray):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _bins_arg(bins: np.ndarray):
    """A step's bins as ah_dir_resolve takes them: int32 or int64 in place
    (the flag says which), anything else widened once."""
    if bins.dtype not in (np.int32, np.int64) or not bins.flags.c_contiguous:
        bins = np.ascontiguousarray(bins, dtype=np.int64)
    return bins, bins.ctypes.data_as(ctypes.c_void_p), int(bins.dtype == np.int32)


def dir_resolve(keys: np.ndarray, bins: np.ndarray, hcode: np.ndarray,
                hbin: np.ndarray, hslot: np.ndarray, boundary: int,
                slot_keys: np.ndarray, slot_bins: np.ndarray, padded=None):
    """Single-pass (key,bin)->slot resolution against the slot directory's
    open-addressing arrays (see cpp ah_dir_resolve). Returns (slots,
    miss_ord, miss_codes, miss_keys, miss_bins, miss_bin_counts) or None
    when the native library is unavailable or a probe wrapped.
    ``miss_bin_counts``: [(bin, first-seen groups of it)] in plain ints, for
    ``dir_claim``, or None where the misses span more bins than it takes.
    ``padded``: (room, index dtype, pad) for a step made to the device's
    shapes: the slots then come as the step's index input, int32 or int64,
    ``room`` long, the entries past the rows at ``pad``; else int64, a row
    each. Raises on 64-bit code collision, matching
    BinSlotDirectory.lookup_or_assign."""
    l = lib()
    if l is None:
        return None
    n = len(keys)
    room, slot_dt, pad = (n, np.int64, 0) if padded is None else padded
    out_slots = np.empty(room, dtype=slot_dt)
    miss_ord = np.empty(n, dtype=np.int64)
    miss_codes = np.empty(n, dtype=np.uint64)
    miss_keys = np.empty(n, dtype=np.int64)
    miss_bins = np.empty(n, dtype=np.int64)
    bin_vals = (ctypes.c_int64 * _DIR_MAX_BINS)()
    bin_counts = (ctypes.c_int64 * _DIR_MAX_BINS)()
    n_bins = ctypes.c_int64()
    bins, bins_p, narrow = _bins_arg(bins)
    rc = l.ah_dir_resolve(
        _i64p(keys), bins_p, narrow, n,
        _u64p(hcode), _i64p(hbin), _i64p(hslot),
        len(hcode), boundary,
        _i64p(slot_keys), _i64p(slot_bins),
        out_slots.ctypes.data, int(out_slots.itemsize == 4), room, pad,
        _i64p(miss_ord),
        _u64p(miss_codes), _i64p(miss_keys), _i64p(miss_bins),
        bin_vals, bin_counts, ctypes.byref(n_bins),
    )
    if rc == -2:
        raise RuntimeError("64-bit (bin,key) code collision in slot directory")
    if rc < 0:
        return None
    m = int(rc)
    nb = n_bins.value
    by_bin = None if nb < 0 else list(zip(bin_vals[:nb], bin_counts[:nb]))
    return out_slots, miss_ord, miss_codes[:m], miss_keys[:m], miss_bins[:m], by_bin


def dir_claim(out_slots: np.ndarray, miss_ord: np.ndarray, miss_codes: np.ndarray,
              miss_keys: np.ndarray, miss_bins: np.ndarray, hcode: np.ndarray,
              hbin: np.ndarray, hslot: np.ndarray, boundary: int,
              slot_keys: np.ndarray, slot_bins: np.ndarray, ranges: list) -> int:
    """Place the first-seen groups one ``dir_resolve`` found and give their
    rows their slots (see cpp ah_dir_claim). ``ranges``: (bin, first slot,
    count) triples in plain ints, a bin's together and in the order its
    slots are to be taken. Writes the directory's arrays and ``out_slots``
    (as ``dir_resolve`` made it, padded or not) in place; returns the rows
    still at -1 (their bin's ranges ran out)."""
    l = lib()
    m = len(miss_codes)
    flat = [v for triple in ranges for v in triple]
    miss_slots = np.empty(m, dtype=np.int64)
    rc = l.ah_dir_claim(
        _u64p(miss_codes), _i64p(miss_keys), _i64p(miss_bins), m,
        _u64p(hcode), _i64p(hbin), _i64p(hslot),
        len(hcode), boundary,
        _i64p(slot_keys), _i64p(slot_bins),
        (ctypes.c_int64 * len(flat))(*flat), len(ranges),
        _i64p(miss_slots), out_slots.ctypes.data, int(out_slots.itemsize == 4),
        _i64p(miss_ord), len(miss_ord),
    )
    if rc < 0:
        raise RuntimeError(f"slot directory claim failed (rc {rc})")
    return int(rc)


def _bin_args(part, n_lanes: int):
    """One bin's ``(keys, lanes)`` as ah_pane_slide takes it: the keys, one
    pointer a lane, the rows, and the arrays the pointers point into (to be
    held until the call is over); a bin that held no row is None."""
    if part is None:
        return None, None, 0, None
    keys, lanes = part
    held = [np.ascontiguousarray(keys)] + [np.ascontiguousarray(a) for a in lanes]
    if len(lanes) != n_lanes or any(a.itemsize != 8 or a.shape != (len(keys),) for a in held):
        raise ValueError("a bin's keys and lanes are 8 bytes wide, a lane a state lane, "
                         "a row a key")
    ptrs = (ctypes.c_void_p * n_lanes)(*[a.ctypes.data for a in held[1:]])
    return _i64p(held[0]), ptrs, len(keys), held


def pane_slide(state: np.ndarray, n: int, add, retire, n_added: int):
    """Slide a window's combined rows by one bin (see cpp ah_pane_slide).
    ``state``: an int64 block of 2 + lanes rows (keys, presence, a row a
    lane), its first ``n`` columns filled; ``add`` / ``retire``: the
    ``(keys, lanes)`` of the bin coming in and of the one going out, every
    lane 8 bytes wide, or None. Returns a new block and its filled columns,
    the state untouched; None where the pass refused what it met (the caller
    combines the window's bins anew) or the library is unavailable."""
    l = lib()
    if l is None:
        return None
    if (state.dtype != np.int64 or state.ndim != 2 or not state.flags.c_contiguous
            or not 0 <= n <= state.shape[1] or not 0 <= n_added <= state.shape[0] - 2):
        raise ValueError("the state is a C-contiguous int64 block of 2 + lanes rows")
    a_keys, a_lanes, a, _held_a = _bin_args(add, state.shape[0] - 2)
    r_keys, r_lanes, r, _held_r = _bin_args(retire, state.shape[0] - 2)
    out = np.empty((state.shape[0], n + a), dtype=np.int64)
    m = l.ah_pane_slide(
        _i64p(state), state.shape[1], n,
        a_keys, a_lanes, a, r_keys, r_lanes, r,
        state.shape[0] - 2, n_added,
        _i64p(out), n + a,
    )
    return None if m < 0 else (out, int(m))


_BIN_KINDS = {"sum": 0, "count": 1, "min": 2, "max": 3}
# distinct bins one ah_bin_combine takes: a batch holds one or two, a
# restore's replay or a stream far out of order a few more
_BIN_COMBINE_BINS = 64


def bin_combine(ts: np.ndarray, bin_micros: int, kinds, lanes):
    """One batch of a keyless aggregate as one partial a bin (see cpp
    ah_bin_combine). ``ts``: the rows' event times; ``lanes[l]``: the input
    of accumulator l, a signed 4- or 8-byte integer array a row long, None
    for a ``count``; ``kinds``: ``sum`` / ``count`` / ``min`` / ``max``.
    Returns (bins, rows of each, [a lane's partial of each as int64]), the
    bins in the order they were met; None where the library is unavailable
    or the batch is not one it takes (over 64 distinct bins, another kind or
    lane type): the caller reduces with numpy."""
    l = lib()
    if l is None:
        return None
    L, cap = len(kinds), _BIN_COMBINE_BINS
    codes = [_BIN_KINDS.get(k, -1) for k in kinds]
    held = [None if a is None else np.ascontiguousarray(a) for a in lanes]
    if any(a is not None and a.dtype.kind != "i" for a in held):
        return None
    ts = np.ascontiguousarray(ts, dtype=np.int64)
    out = np.empty((2 + L, cap), dtype=np.int64)  # bins, rows, a row a lane
    m = l.ah_bin_combine(
        _i64p(ts), len(ts), bin_micros, L,
        (ctypes.c_int32 * L)(*codes),
        (ctypes.c_int32 * L)(*[0 if a is None else a.dtype.itemsize for a in held]),
        (ctypes.c_void_p * L)(*[None if a is None else a.ctypes.data for a in held]),
        _i64p(out), cap,
    )
    if m < 0:
        return None
    return out[0, :m], out[1, :m], list(out[2:, :m])


# ---------------------------------------------------- a keyed aggregate's step

_I64 = np.dtype(np.int64)
_STEP_TYPES = {np.dtype(np.int32): 0, _I64: 1, np.dtype(np.float32): 2, np.dtype(np.float64): 3}


class MadeStep:
    """What ``StepMaker.make`` hands back: the kept rows' ``keys`` (int64)
    and relative bins ``rel`` (int32), both a step's width long with
    ``rows`` of them filled; ``lanes``, one entry an accumulator: a lane's
    values in its dtype, padded with its identity to the width, or None for
    a lane that ships nothing; the rows ``late``; the distinct ``bins``
    among the kept (plain ints, in the order met); and the bin space's
    ``base``."""

    __slots__ = ("rows", "late", "keys", "rel", "lanes", "bins", "base")


class StepMaker:
    """One window aggregate's binding of cpp ah_step_make: what of the call
    is the same step after step (the lanes' types and identities) is made
    once, when the operator has seen its first batch's columns.

    ``columns[l]`` is the dtype of the column lane l reads (a plain column
    of the batches, or an expression's values evaluated to the lane's own
    dtype), None for a lane that ships nothing (a ``count`` outside merge
    mode). ``takes`` says whether the pass takes such lanes at all: 4- or
    8-byte signed integers and floats, and no float column into an integer
    lane."""

    def __init__(self, kinds, dtypes, columns):
        from ..ops.aggregate import _identity

        self.dtypes = [np.dtype(d) for d in dtypes]
        self.columns = [None if c is None else np.dtype(c) for c in columns]
        self.shipped = [l for l, c in enumerate(self.columns) if c is not None]
        L = len(self.dtypes)
        codes = lambda ds: (ctypes.c_int32 * L)(*[_STEP_TYPES.get(d, -1) if d is not None else 0
                                                  for d in ds])
        self._n = L
        self._src, self._dst = codes(self.columns), codes(self.dtypes)
        idents = [_identity(k, d) for k, d in zip(kinds, self.dtypes)]
        self._ident_i = (ctypes.c_int64 * L)(*[int(v) if d.kind == "i" else 0
                                               for v, d in zip(idents, self.dtypes)])
        self._ident_f = (ctypes.c_double * L)(*[float(v) if d.kind == "f" else 0.0
                                                for v, d in zip(idents, self.dtypes)])
        # read at once after each call, on the one thread that makes this
        # operator's steps: kept between them
        self._info = (ctypes.c_int64 * (3 + _STEP_MAX_BINS))()

    @staticmethod
    def takes(kinds, dtypes, columns) -> bool:
        if lib() is None:
            return False
        for k, d, c in zip(kinds, dtypes, columns):
            if k not in ("sum", "count", "min", "max") or np.dtype(d) not in _STEP_TYPES:
                return False
            if c is not None and (np.dtype(c) not in _STEP_TYPES
                                  or (np.dtype(c).kind == "f" and np.dtype(d).kind == "i")):
                return False
        return True

    def make(self, pieces, bin_micros: int, base: Optional[int],
             late_before: Optional[int], room: int) -> Optional[MadeStep]:
        """One step from ``pieces``, a list of (event times, keys or None,
        [the column lane l reads, for each shipped lane]) of equal rows a
        piece, ``room`` rows at most in all. None where the pass takes no
        such step (a column of another dtype or layout than the first
        batch's, over 64 distinct bins): the caller runs its numpy hook."""
        P, L = len(pieces), self._n
        rows, ts, keys, cols = [], [], [], [0] * (L * P)
        for p, (t, k, lanes) in enumerate(pieces):
            if t.dtype != _I64 or t.strides != (8,):
                return None
            rows.append(len(t))
            ts.append(t.ctypes.data)
            if k is not None:
                if k.dtype.kind not in "iu" or k.strides != (8,):  # 8-byte hashes, read as they lie
                    return None
                keys.append(k.ctypes.data)
            for l, c in zip(self.shipped, lanes):
                want = self.columns[l]
                if c.dtype != want or c.strides != (want.itemsize,):
                    return None
                cols[l * P + p] = c.ctypes.data
        if keys and len(keys) != P:
            return None
        out = MadeStep()
        out.keys = np.empty(room, dtype=np.int64)
        out.rel = np.empty(room, dtype=np.int32)
        out.lanes = [None] * L
        for l in self.shipped:
            out.lanes[l] = np.empty(room, dtype=self.dtypes[l])
        info = self._info
        info[2] = base or 0
        m = _lib_kept.ah_step_make(
            P, (ctypes.c_int64 * P)(*rows), (ctypes.c_void_p * P)(*ts),
            (ctypes.c_void_p * P)(*keys) if keys else None,
            L, self._src, self._dst, (ctypes.c_void_p * (L * P))(*cols),
            bin_micros, base is not None, late_before is not None, late_before or 0,
            room, _i64p(out.keys), out.rel.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
            (ctypes.c_void_p * L)(*[None if a is None else a.ctypes.data for a in out.lanes]),
            self._ident_i, self._ident_f, info)
        if m < 0:
            return None
        out.rows, out.late, out.base = m, info[0], info[2]
        out.bins = info[3:3 + info[1]]
        return out


# -------------------------------------------------------------- JSON lines

_KIND = {"int64": 0, "timestamp": 0, "int32": 0, "uint64": 0,
         "float64": 1, "float32": 1, "bool": 2, "string": 3}


def parse_json_lines(data: bytes, fields: list[tuple[str, str]],
                     max_rows: int) -> Optional[dict[str, np.ndarray]]:
    """Parse newline-delimited flat JSON objects into columns.
    fields: (name, dtype) with dtypes from batch.Schema. Returns None when
    the native library is unavailable or input is malformed (caller falls
    back to the Python parser, which produces the precise error)."""
    l = lib()
    if l is None:
        return None
    n_cols = len(fields)
    if n_cols > 64:
        return None
    kinds = np.array([_KIND.get(d, 4) for _n, d in fields], dtype=np.int32)
    names_blob = b"".join(n.encode() + b"\x00" for n, _d in fields)
    int_arrays, f64_arrays, bool_arrays, off_arrays = {}, {}, {}, {}
    i64p = ctypes.POINTER(ctypes.c_int64)
    f64p = ctypes.POINTER(ctypes.c_double)
    u8p = ctypes.POINTER(ctypes.c_uint8)
    int_ptrs = (i64p * n_cols)()
    f64_ptrs = (f64p * n_cols)()
    bool_ptrs = (u8p * n_cols)()
    off_ptrs = (i64p * n_cols)()
    for c, (_name, _d) in enumerate(fields):
        k = kinds[c]
        if k == 0:
            a = np.zeros(max_rows, dtype=np.int64)
            int_arrays[c] = a
            int_ptrs[c] = a.ctypes.data_as(i64p)
        elif k == 1:
            a = np.zeros(max_rows, dtype=np.float64)
            f64_arrays[c] = a
            f64_ptrs[c] = a.ctypes.data_as(f64p)
        elif k == 2:
            a = np.zeros(max_rows, dtype=np.uint8)
            bool_arrays[c] = a
            bool_ptrs[c] = a.ctypes.data_as(u8p)
        elif k == 3:
            a = np.zeros(max_rows + 1, dtype=np.int64)
            off_arrays[c] = a
            off_ptrs[c] = a.ctypes.data_as(i64p)
    arena = ctypes.c_char_p()
    arena_len = ctypes.c_int64()
    n = l.ah_parse_json_lines(
        data, len(data), n_cols, names_blob,
        kinds.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)), max_rows,
        int_ptrs, f64_ptrs, bool_ptrs, off_ptrs,
        ctypes.byref(arena), ctypes.byref(arena_len),
    )
    if n < 0:
        return None
    try:
        arena_bytes = ctypes.string_at(arena, arena_len.value) if arena_len.value else b""
    finally:
        if arena:
            l.ah_free(arena)
    out: dict[str, np.ndarray] = {}
    from ..batch import Field

    for c, (name, dtype) in enumerate(fields):
        k = kinds[c]
        if k == 0:
            out[name] = int_arrays[c][:n].astype(Field(name, dtype).numpy_dtype(), copy=False)
        elif k == 1:
            out[name] = f64_arrays[c][:n].astype(Field(name, dtype).numpy_dtype(), copy=False)
        elif k == 2:
            out[name] = bool_arrays[c][:n].astype(bool)
        elif k == 3:
            offs = off_arrays[c]
            col = np.empty(n, dtype=object)
            for i in range(n):
                col[i] = arena_bytes[offs[i]:offs[i + 1]].decode("utf-8")
            out[name] = col
    return out


# -------------------------------------------------------------- data plane


class DataPlaneError(RuntimeError):
    pass


MSG_DATA = 0
MSG_SIGNAL = 1


class DataPlaneListener:
    """Server half (reference network_manager.rs InNetworkLink)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0):
        l = lib()
        if l is None:
            raise DataPlaneError("native library unavailable")
        self._l = l
        self.fd = l.dp_listen(host.encode(), port)
        if self.fd < 0:
            raise DataPlaneError(f"dp_listen failed: {self.fd}")
        self.port = l.dp_bound_port(self.fd)

    def accept(self) -> "DataPlaneConn":
        fd = self._l.dp_accept(self.fd)
        if fd < 0:
            raise DataPlaneError("dp_accept failed")
        return DataPlaneConn(fd)

    def close(self) -> None:
        self._l.dp_close(self.fd)


class DataPlaneConn:
    """One framed TCP link multiplexing all quads between two workers
    (reference OutNetworkLink, network_manager.rs:211)."""

    def __init__(self, fd: int):
        self._l = lib()
        self.fd = fd
        # one connection is shared by every sending task thread on this
        # worker pair; header+payload are two writes and must not interleave
        from ..obs.lockorder import make_lock  # lazy: keep native import-light

        self._send_lock = make_lock("DataPlaneConn._send_lock")

    @staticmethod
    def connect(host: str, port: int, retries: int = 10, backoff_ms: int = 50) -> "DataPlaneConn":
        l = lib()
        if l is None:
            raise DataPlaneError("native library unavailable")
        fd = l.dp_connect(host.encode(), port, retries, backoff_ms)
        if fd < 0:
            raise DataPlaneError(f"dp_connect failed: {fd}")
        return DataPlaneConn(fd)

    def send(self, quad: tuple[int, int, int, int], msg_type: int, payload: bytes) -> None:
        with self._send_lock:
            rc = self._l.dp_send_frame(
                self.fd, quad[0], quad[1], quad[2], quad[3], msg_type,
                payload, len(payload),
            )
        if rc != 0:
            raise DataPlaneError("dp_send_frame failed (peer closed?)")

    def recv(self):
        """-> (quad, msg_type, payload bytes) or None on clean close."""
        header = (ctypes.c_uint32 * 6)()
        rc = self._l.dp_recv_header(self.fd, header)
        if rc == -1:
            return None
        if rc != 0:
            raise DataPlaneError(f"dp_recv_header failed: {rc}")
        n = header[5]
        buf = ctypes.create_string_buffer(n) if n else None
        if n:
            if self._l.dp_recv_payload(self.fd, buf, n) != 0:
                raise DataPlaneError("dp_recv_payload failed")
        quad = (header[0], header[1], header[2], header[3])
        return quad, header[4], (buf.raw[:n] if n else b"")

    def close(self) -> None:
        self._l.dp_close(self.fd)
