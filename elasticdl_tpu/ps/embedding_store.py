"""Embedding store bindings: native C++ store with a numpy fallback.

The native library (native/embedding_store.cc) is the TPU-host
equivalent of the reference's Go PS runtime (lazy hash-map tables +
sparse optimizer kernels, §2.2 of SURVEY.md). The numpy implementation
mirrors it exactly and serves as both a fallback when no C++ toolchain
exists and the reference semantics for tests.
"""

import ctypes
import os
import subprocess
import sys
import threading

import numpy as np

from elasticdl_tpu.common.log_utils import default_logger as _logger_factory

logger = _logger_factory("elasticdl_tpu.ps.embedding_store")

_NATIVE_DIR = os.path.join(os.path.dirname(__file__), "..", "native")
_SO_PATH = os.path.abspath(os.path.join(_NATIVE_DIR, "libedl_embedding.so"))

# ABI clock this binding targets (edl_store_abi_version in
# native/embedding_store.cc). A .so reporting anything else — or
# missing the symbol entirely (pre-clock builds) — is a stale artifact
# from another tree: the loader rebuilds it once, and on any failure
# falls back to the numpy store instead of raising mid-job.
# ABI 3: drop_rows/drop_table (embedding lifecycle eviction, ISSUE 12).
# ABI 4: dirty-row tracking + export_dirty/dirty_count/clear_dirty
# (incremental checkpoints, ISSUE 13).
_EXPECTED_ABI = 4

# TensorBlob wire dtype name -> WireDtype enum in embedding_store.cc;
# the only payload dtypes the blob fast paths accept — anything else
# routes through the numpy-array slow path. BLOB_ITEMSIZE is the
# companion bytes-per-element table: every size computation derives
# from it (servicer gate included) so a new wire dtype cannot desync
# the shape checks.
BLOB_DTYPE_CODES = {"float32": 0, "bfloat16": 1, "float16": 2}
BLOB_ITEMSIZE = {"float32": 4, "bfloat16": 2, "float16": 2}

# the packed wire encoding is little-endian int64; the native fast
# paths read it as host int64, so they are only offered on LE hosts
_LITTLE_ENDIAN = sys.byteorder == "little"

OPTIMIZER_DEFAULTS = dict(
    lr=0.01, momentum=0.9, beta1=0.9, beta2=0.999, epsilon=1e-8
)


# optimizer -> slot rows per weight row; must match OptConfig::slots in
# native/embedding_store.cc
OPT_SLOT_COUNTS = {
    "sgd": 0, "momentum": 1, "nesterov": 1,
    "adagrad": 1, "adam": 2, "amsgrad": 3,
}

# row initializer -> InitKind in native/embedding_store.cc (reference
# go/pkg/common/initializer.go:25-155; "zeros" is constant 0)
INIT_KINDS = {
    "uniform": 0, "constant": 1, "normal": 2, "truncated_normal": 3,
}


def parse_initializer(spec, default_scale=0.05):
    """Wire-format initializer string -> (kind, param).

    Accepts "0.05" (bare scale = uniform, the original wire format),
    "normal:0.01", "constant:1.5", "zeros", or "uniform".
    """
    if not spec:
        return "uniform", default_scale
    spec = str(spec)
    kind, _, param = spec.partition(":")
    kind = kind.strip().lower()
    try:
        # bare number: legacy uniform-scale encoding
        return "uniform", float(kind)
    except ValueError:
        pass
    if kind == "zeros":
        return "constant", 0.0
    if kind not in INIT_KINDS:
        raise ValueError("unknown embedding initializer %r" % spec)
    return kind, float(param) if param else default_scale


def _normalize_opt_type(opt_type, kwargs):
    """Fold nesterov=True / amsgrad=True kwargs into the variant opt
    type strings the kernels dispatch on (reference optimizer.go
    supports Momentum+nesterov and Adam+amsgrad as flags)."""
    opt_type = opt_type.lower()
    if kwargs.pop("nesterov", False):
        if opt_type != "momentum":
            raise ValueError("nesterov requires the momentum optimizer")
        opt_type = "nesterov"
    if kwargs.pop("amsgrad", False):
        if opt_type != "adam":
            raise ValueError("amsgrad requires the adam optimizer")
        opt_type = "amsgrad"
    return opt_type


def _build_native(force=False):
    cmd = ["make", "-C", os.path.abspath(_NATIVE_DIR)]
    if force:
        cmd.insert(1, "-B")
    subprocess.run(cmd, check=True, capture_output=True)


def _cdll_fresh(path):
    """CDLL through a temp copy. dlopen dedups by pathname, so
    re-loading ``_SO_PATH`` after an in-place rebuild returns the
    ALREADY-MAPPED stale library and the ABI re-check could never
    pass. A copy at a fresh path (new name, new inode) forces a
    genuinely new mapping; the dirent is unlinked immediately — the
    mapping keeps the file alive for the process lifetime."""
    import shutil
    import tempfile

    fd, tmp = tempfile.mkstemp(prefix="libedl_embedding-", suffix=".so")
    os.close(fd)
    try:
        shutil.copy2(path, tmp)
        return ctypes.CDLL(tmp)
    finally:
        try:
            os.unlink(tmp)
        except OSError:
            pass


def _abi_of(lib):
    """The loaded .so's ABI clock, or None when the symbol is absent
    (a pre-clock build — ABI 1 by definition, still a mismatch)."""
    try:
        fn = lib.edl_store_abi_version
    except AttributeError:
        return None
    fn.restype = ctypes.c_int64
    fn.argtypes = []
    return int(fn())


def _load_native():
    """Build/load/bind the native store, or return None (numpy
    fallback). NEVER raises: a missing toolchain, an undefined symbol
    from a half-built .so, or ABI drift from a stale artifact all log
    once (native_lib caches the failure) and degrade — a PS must not
    crash mid-job because its cached .so predates this binding."""
    try:
        return _load_native_checked()
    except Exception as e:  # truly defensive: any surprise degrades
        logger.warning(
            "Native embedding store unavailable (%s); using the numpy "
            "store", e,
        )
        return None


def _load_native_checked():
    # Always run make: a no-op when the .so is newer than its source,
    # a rebuild when a stale binary was left in the tree (the .so is
    # git-ignored, so nothing else ties it to this checkout's source).
    try:
        _build_native()
    except Exception as e:
        logger.warning("Native embedding store build failed: %s", e)
        if not os.path.exists(_SO_PATH):
            return None
        # no toolchain at run time but a prebuilt .so (container
        # image): load it; the ABI check below still guards it
    try:
        lib = ctypes.CDLL(_SO_PATH)
    except OSError as e:
        logger.warning("Native embedding store load failed: %s", e)
        return None
    abi = _abi_of(lib)
    if abi != _EXPECTED_ABI:
        # stale .so (another tree / older release): rebuild once from
        # the sources next to it, then re-check
        logger.warning(
            "Native embedding store ABI drift (have %s, want %d); "
            "rebuilding %s", abi, _EXPECTED_ABI, _SO_PATH,
        )
        try:
            _build_native(force=True)
            # NOT a plain CDLL(_SO_PATH): that path is already mapped
            # (the stale load above) and dlopen would return the old
            # library — load the rebuilt file through a fresh copy
            lib = _cdll_fresh(_SO_PATH)
        except Exception as e:
            logger.warning(
                "Native embedding store rebuild failed (%s); using the "
                "numpy store", e,
            )
            return None
        abi = _abi_of(lib)
        if abi != _EXPECTED_ABI:
            logger.warning(
                "Native embedding store still at ABI %s after rebuild "
                "(want %d); using the numpy store", abi, _EXPECTED_ABI,
            )
            return None
    try:
        _bind_native(lib)
    except AttributeError as e:
        # a symbol this binding needs is missing: fall back instead of
        # surfacing an AttributeError from deep inside a push RPC
        logger.warning(
            "Native embedding store is missing a symbol (%s); using "
            "the numpy store", e,
        )
        return None
    return lib


def _bind_native(lib):
    lib.edl_store_create.restype = ctypes.c_void_p
    lib.edl_store_create.argtypes = [ctypes.c_uint64]
    lib.edl_store_destroy.argtypes = [ctypes.c_void_p]
    lib.edl_store_set_optimizer.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        # doubles, not floats: the kernels round each hyperparameter
        # to f32 exactly where numpy's weak-scalar promotion does, so
        # they need the python float's full value (ABI 2)
        ctypes.c_double,
        ctypes.c_double,
        ctypes.c_double,
        ctypes.c_double,
        ctypes.c_double,
    ]
    lib.edl_store_create_table.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_float,
    ]
    lib.edl_store_create_table_init.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.c_float,
    ]
    lib.edl_store_lookup.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_float),
    ]
    lib.edl_store_push_gradients.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64,
        ctypes.c_double,
    ]
    lib.edl_store_apply_blob.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_double,
        ctypes.c_int,
    ]
    lib.edl_store_lookup_cast.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_void_p,
        ctypes.c_int,
    ]
    lib.edl_store_import_blob.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_void_p,
        ctypes.c_int,
        ctypes.c_int,
        ctypes.c_int,
    ]
    lib.edl_store_drop_rows.restype = ctypes.c_int64
    lib.edl_store_drop_rows.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
    ]
    lib.edl_store_drop_table.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.edl_store_table_size.restype = ctypes.c_int64
    lib.edl_store_table_size.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.edl_store_version.restype = ctypes.c_int64
    lib.edl_store_version.argtypes = [ctypes.c_void_p]
    lib.edl_store_bump_version.argtypes = [ctypes.c_void_p]
    if hasattr(lib, "edl_store_set_version"):  # absent in older builds
        lib.edl_store_set_version.argtypes = [
            ctypes.c_void_p, ctypes.c_int64,
        ]
    lib.edl_store_export.restype = ctypes.c_int64
    lib.edl_store_export.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64,
    ]
    lib.edl_store_table_slots.restype = ctypes.c_int
    lib.edl_store_table_slots.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.edl_store_export_full.restype = ctypes.c_int64
    lib.edl_store_export_full.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
    ]
    lib.edl_store_import_full.restype = ctypes.c_int
    lib.edl_store_import_full.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.c_int,
    ]
    lib.edl_store_import.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_float),
        ctypes.c_int64,
        ctypes.c_int,
        ctypes.c_int,
    ]
    lib.edl_store_dirty_count.restype = ctypes.c_int64
    lib.edl_store_dirty_count.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.edl_store_dead_count.restype = ctypes.c_int64
    lib.edl_store_dead_count.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.edl_store_export_dirty.restype = ctypes.c_int64
    lib.edl_store_export_dirty.argtypes = [
        ctypes.c_void_p,
        ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_float),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int64,
        ctypes.c_int64,
        ctypes.POINTER(ctypes.c_int64),
        ctypes.c_int,
    ]
    lib.edl_store_clear_dirty.restype = ctypes.c_int
    lib.edl_store_clear_dirty.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    return lib


def _as_i64(ids):
    """int64 C-contiguous view of ``ids``, converting ONLY when the
    caller doesn't already hold one. Wire-path callers pass
    ``np.frombuffer`` views of packed id blobs (read-only is fine —
    the native side never writes through these pointers), and the old
    unconditional ``ascontiguousarray`` re-walked those through
    numpy's conversion machinery on every call."""
    a = ids if isinstance(ids, np.ndarray) else np.asarray(ids)
    if a.dtype == np.int64 and a.flags.c_contiguous:
        return a
    return np.ascontiguousarray(a, dtype=np.int64)


def _as_f32(values):
    """float32 C-contiguous view of ``values``; same contract as
    :func:`_as_i64`."""
    a = values if isinstance(values, np.ndarray) else np.asarray(values)
    if a.dtype == np.float32 and a.flags.c_contiguous:
        return a
    return np.ascontiguousarray(a, dtype=np.float32)


def _i64_ptr(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _f32_ptr(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


_native_lib = None
_native_lock = threading.Lock()


def native_lib():
    global _native_lib
    with _native_lock:
        if _native_lib is None:
            _native_lib = _load_native() or False
    return _native_lib or None


class NativeEmbeddingStore:
    """ctypes wrapper over the C++ store."""

    def __init__(self, seed=0, lib=None):
        self._lib = lib or native_lib()
        if self._lib is None:
            raise RuntimeError("native embedding store unavailable")
        self._handle = ctypes.c_void_p(self._lib.edl_store_create(seed))
        self._dims = {}
        self._opt_type = "sgd"

    def __del__(self):
        handle = getattr(self, "_handle", None)
        if handle:
            self._lib.edl_store_destroy(handle)
            self._handle = None

    def set_optimizer(self, opt_type, **kwargs):
        opt_type = _normalize_opt_type(opt_type, kwargs)
        args = dict(OPTIMIZER_DEFAULTS)
        args.update(kwargs)
        rc = self._lib.edl_store_set_optimizer(
            self._handle,
            opt_type.lower().encode(),
            args["lr"],
            args["momentum"],
            args["beta1"],
            args["beta2"],
            args["epsilon"],
        )
        if rc == -2:
            raise RuntimeError(
                "cannot change the optimizer after tables exist (slot "
                "memory is sized at table creation)"
            )
        if rc != 0:
            raise ValueError("unsupported sparse optimizer %r" % opt_type)
        # only after the native call succeeded — a failed swap must not
        # desync the checkpoint opt tag from the live kernels
        self._opt_type = opt_type

    def create_table(self, name, dim, init_scale=0.05, initializer="uniform"):
        if initializer == "zeros":
            initializer, init_scale = "constant", 0.0
        rc = self._lib.edl_store_create_table_init(
            self._handle, name.encode(), dim,
            INIT_KINDS[initializer], init_scale,
        )
        if rc != 0:
            raise ValueError(
                "table %r exists with a different dim" % name
            )
        self._dims[name] = dim

    def lookup(self, name, ids):
        ids = _as_i64(ids)
        dim = self._dims[name]
        out = np.empty((ids.size, dim), dtype=np.float32)
        rc = self._lib.edl_store_lookup(
            self._handle,
            name.encode(),
            _i64_ptr(ids),
            ids.size,
            _f32_ptr(out),
        )
        if rc != 0:
            raise KeyError(name)
        return out

    def lookup_blob(self, name, ids, wire_dtype_name=None):
        """Batched lookup emitted directly at the wire dtype: one
        GIL-released C call does lazy-init + gather + (bf16/fp16)
        downcast, returning the payload bytes a TensorBlob carries.
        Returns ``(content bytes, dtype name)``; the downcast is
        round-to-nearest-even, bit-identical to numpy ``astype``."""
        dtype_name = wire_dtype_name or "float32"
        code = BLOB_DTYPE_CODES[dtype_name]
        ids = _as_i64(ids)
        dim = self._dims[name]
        out = np.empty(
            ids.size * dim * BLOB_ITEMSIZE[dtype_name], dtype=np.uint8
        )
        rc = self._lib.edl_store_lookup_cast(
            self._handle,
            name.encode(),
            _i64_ptr(ids),
            ids.size,
            out.ctypes.data_as(ctypes.c_void_p),
            code,
        )
        if rc != 0:
            raise KeyError(name)
        return out.tobytes(), dtype_name

    def push_gradients(self, name, ids, grads, lr_scale=1.0):
        ids = _as_i64(ids)
        grads = _as_f32(grads)
        rc = self._lib.edl_store_push_gradients(
            self._handle,
            name.encode(),
            _i64_ptr(ids),
            _f32_ptr(grads),
            ids.size,
            lr_scale,
        )
        if rc != 0:
            raise KeyError(name)

    def push_gradients_blob(self, name, ids, content, dtype_name,
                            lr_scale=1.0, dedup=True):
        """Wire-blob fast path: deserialize (+fp32 upcast), dedup, and
        apply one table's pushed gradients in a single GIL-released C
        call. ``ids``: int64 array (a read-only ``np.frombuffer`` view
        of the request's packed ids_blob is the intended input);
        ``content``: the TensorBlob payload bytes at ``dtype_name``
        ([n, dim] row-major). ``dedup=True`` merges duplicate ids with
        the sort+reduceat-equivalent segment sum before the single
        optimizer apply per unique id — bit-identical to
        ``deduplicate_indexed_slices`` + the numpy store's apply."""
        code = BLOB_DTYPE_CODES[dtype_name]
        ids = _as_i64(ids)
        buf = np.frombuffer(content, dtype=np.uint8)
        expected = ids.size * self._dims[name] * BLOB_ITEMSIZE[dtype_name]
        if buf.size != expected:
            raise ValueError(
                "push_gradients_blob: %d payload bytes for %d ids of "
                "table %r (want %d)" % (buf.size, ids.size, name, expected)
            )
        rc = self._lib.edl_store_apply_blob(
            self._handle,
            name.encode(),
            _i64_ptr(ids),
            ids.size,
            buf.ctypes.data_as(ctypes.c_void_p),
            code,
            lr_scale,
            1 if dedup else 0,
        )
        if rc == -2:
            raise ValueError("unsupported blob dtype %r" % dtype_name)
        if rc != 0:
            raise KeyError(name)

    def import_blob(self, name, ids, content, dtype_name,
                    shard_id=0, shard_num=0):
        """Raw row import straight from wire bytes (device-tier
        writebacks): values at ``dtype_name`` upcast into the fp32
        master rows, last-write-wins on duplicate ids, optional id-mod
        shard filter — one GIL-released C call."""
        code = BLOB_DTYPE_CODES[dtype_name]
        ids = _as_i64(ids)
        buf = np.frombuffer(content, dtype=np.uint8)
        expected = ids.size * self._dims[name] * BLOB_ITEMSIZE[dtype_name]
        if buf.size != expected:
            raise ValueError(
                "import_blob: %d payload bytes for %d ids of table %r "
                "(want %d)" % (buf.size, ids.size, name, expected)
            )
        rc = self._lib.edl_store_import_blob(
            self._handle,
            name.encode(),
            _i64_ptr(ids),
            ids.size,
            buf.ctypes.data_as(ctypes.c_void_p),
            code,
            shard_id,
            shard_num,
        )
        if rc == -2:
            raise ValueError("unsupported blob dtype %r" % dtype_name)
        if rc != 0:
            raise KeyError(name)

    def drop_rows(self, name, ids):
        """Delete rows outright — weights, slots, AND per-row step
        counts — so a later re-admission of the id starts from the
        initializer like a never-seen id (lifecycle eviction, ISSUE
        12). Absent ids are not an error (a sweep may race a restore);
        returns the number of rows actually dropped."""
        ids = _as_i64(ids)
        dropped = self._lib.edl_store_drop_rows(
            self._handle, name.encode(), _i64_ptr(ids), ids.size
        )
        if dropped < 0:
            raise KeyError(name)
        return int(dropped)

    def drop_table(self, name):
        """Drop a whole table (administrative; quiesce traffic first —
        see edl_store_drop_table)."""
        rc = self._lib.edl_store_drop_table(self._handle, name.encode())
        if rc != 0:
            raise KeyError(name)
        self._dims.pop(name, None)

    def table_size(self, name):
        return int(self._lib.edl_store_table_size(self._handle, name.encode()))

    @property
    def version(self):
        return int(self._lib.edl_store_version(self._handle))

    def bump_version(self):
        self._lib.edl_store_bump_version(self._handle)

    def set_version(self, version):
        """Re-anchor the version clock (checkpoint auto-restore)."""
        if hasattr(self._lib, "edl_store_set_version"):
            self._lib.edl_store_set_version(self._handle, int(version))
            return
        # older .so without the setter: bounded catch-up loop
        while self.version < version:
            self.bump_version()

    def table_names(self):
        return list(self._dims)

    def table_dim(self, name):
        return self._dims[name]

    def export_table(self, name):
        count = self._lib.edl_store_export(
            self._handle, name.encode(), None, None, 0
        )
        dim = self._dims[name]
        ids = np.empty((count,), dtype=np.int64)
        values = np.empty((count, dim), dtype=np.float32)
        got = self._lib.edl_store_export(
            self._handle,
            name.encode(),
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            values.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            count,
        )
        return ids[:got], values[:got]

    def import_table(self, name, ids, values, shard_id=0, shard_num=0):
        ids = _as_i64(ids)
        values = _as_f32(values)
        rc = self._lib.edl_store_import(
            self._handle,
            name.encode(),
            _i64_ptr(ids),
            _f32_ptr(values),
            ids.size,
            shard_id,
            shard_num,
        )
        if rc != 0:
            raise KeyError(name)

    @property
    def opt_type(self):
        return self._opt_type

    def table_slots(self, name):
        n = self._lib.edl_store_table_slots(self._handle, name.encode())
        if n < 0:
            raise KeyError(name)
        return n

    def export_table_full(self, name):
        """Full train state: (ids, rows [n, (1+slots)*dim], steps [n])."""
        count = self._lib.edl_store_export_full(
            self._handle, name.encode(), None, None, None, 0
        )
        row_floats = self._dims[name] * (1 + self.table_slots(name))
        ids = np.empty((count,), dtype=np.int64)
        rows = np.empty((count, row_floats), dtype=np.float32)
        steps = np.empty((count,), dtype=np.int64)
        got = self._lib.edl_store_export_full(
            self._handle,
            name.encode(),
            ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            rows.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            steps.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            count,
        )
        return ids[:got], rows[:got], steps[:got]

    def dirty_count(self, name):
        """Rows a delta export would currently carry (gauge/sizing)."""
        n = self._lib.edl_store_dirty_count(self._handle, name.encode())
        if n < 0:
            raise KeyError(name)
        return int(n)

    def export_table_dirty(self, name, clear=True):
        """Snapshot-and-clear dirty export — the delta-checkpoint
        primitive (ISSUE 13). One GIL-released C call under the
        per-table lock exports every row mutated (or first
        materialized) since the last export — ids ascending, full
        train state like :meth:`export_table_full` — plus the dead-id
        tombstones from ``drop_rows``, then clears both sets. Returns
        ``(ids, rows, steps, dead_ids)``. Traffic between the sizing
        probe and the fill retries via the -3 protocol, so nothing is
        ever lost or double-cleared."""
        dim = self._dims[name]
        row_floats = dim * (1 + self.table_slots(name))
        dead_out = ctypes.c_int64(0)
        while True:
            count = self._lib.edl_store_export_dirty(
                self._handle, name.encode(),
                None, None, None, None, 0, 0,
                ctypes.byref(dead_out), 0,
            )
            if count < 0:
                raise KeyError(name)
            # slack absorbs rows dirtied between probe and fill; a
            # burst bigger than the slack returns -3 and re-probes
            cap = int(count) + 1024
            dead_cap = int(dead_out.value) + 1024
            ids = np.empty((cap,), dtype=np.int64)
            rows = np.empty((cap, row_floats), dtype=np.float32)
            steps = np.empty((cap,), dtype=np.int64)
            dead = np.empty((dead_cap,), dtype=np.int64)
            got = self._lib.edl_store_export_dirty(
                self._handle, name.encode(),
                _i64_ptr(ids),
                _f32_ptr(rows),
                _i64_ptr(steps),
                _i64_ptr(dead),
                cap, dead_cap,
                ctypes.byref(dead_out), 1 if clear else 0,
            )
            if got == -3:
                continue
            if got < 0:
                raise KeyError(name)
            return (
                ids[:got], rows[:got], steps[:got],
                dead[: int(dead_out.value)],
            )

    def clear_dirty(self, name):
        """Drop all dirty/dead bookkeeping (taken before a full base
        export: the base carries complete state)."""
        rc = self._lib.edl_store_clear_dirty(self._handle, name.encode())
        if rc != 0:
            raise KeyError(name)

    def import_table_full(self, name, ids, rows, steps,
                          shard_id=0, shard_num=0):
        """Inverse of export_table_full; a slot-layout mismatch (the
        optimizer changed between save and restore) degrades to a
        weights-only import."""
        ids = _as_i64(ids)
        rows = _as_f32(rows)
        steps = _as_i64(steps)
        rc = self._lib.edl_store_import_full(
            self._handle,
            name.encode(),
            _i64_ptr(ids),
            _f32_ptr(rows),
            _i64_ptr(steps),
            ids.size,
            rows.shape[1] if rows.ndim == 2 else 0,
            shard_id,
            shard_num,
        )
        if rc == -2:
            raise ValueError(
                "import_table_full: rows must be [n, (1+slots)*dim] = "
                "[n, %d] for table %r"
                % (self._dims[name] * (1 + self.table_slots(name)), name)
            )
        if rc != 0:
            raise KeyError(name)


class NumpyEmbeddingStore:
    """Pure-python twin of the native store (same semantics)."""

    def __init__(self, seed=0):
        self._seed = seed
        # per-table RNG, like the native store: lazy-init draws are
        # deterministic regardless of the order tables are pulled in
        # (prepare() fans out per-table pulls concurrently)
        self._rngs = {}
        self._tables = {}  # name -> {id: weight row}
        self._slots = {}  # name -> {id: slot array [slots, dim]}
        self._steps = {}  # name -> {id: step count}
        # incremental-checkpoint bookkeeping, the native store's twin
        # (ISSUE 13): _dirty = resident ids mutated/materialized since
        # the last dirty export, _dead = ids dropped since then
        # (tombstones). _dirty is a subset of the resident ids and
        # disjoint from _dead — drops move ids dirty->dead, a
        # re-materialization moves them back.
        self._dirty = {}  # name -> set(id)
        self._dead = {}  # name -> set(id)
        self._meta = {}  # name -> (dim, init_scale)
        self._opt = ("sgd", dict(OPTIMIZER_DEFAULTS))
        self._lock = threading.Lock()
        self.version = 0

    def set_optimizer(self, opt_type, **kwargs):
        opt_type = _normalize_opt_type(opt_type, kwargs)
        if opt_type not in OPT_SLOT_COUNTS:
            raise ValueError("unsupported sparse optimizer %r" % opt_type)
        if self._meta:
            # Parity with the native store: slot layout is fixed at
            # table creation.
            raise RuntimeError(
                "cannot change the optimizer after tables exist (slot "
                "memory is sized at table creation)"
            )
        args = dict(OPTIMIZER_DEFAULTS)
        args.update(kwargs)
        self._opt = (opt_type, args)

    def create_table(self, name, dim, init_scale=0.05, initializer="uniform"):
        if initializer == "zeros":
            initializer, init_scale = "constant", 0.0
        if initializer not in INIT_KINDS:
            raise ValueError("unknown embedding initializer %r" % initializer)
        with self._lock:
            if name in self._meta:
                if self._meta[name][0] != dim:
                    raise ValueError(
                        "table %r exists with a different dim" % name
                    )
                # adopt the (possibly updated) scale so restore-then-
                # register keeps the model's configured init
                self._meta[name] = (dim, init_scale, initializer)
                return
            self._meta[name] = (dim, init_scale, initializer)
            self._tables[name] = {}
            self._slots[name] = {}
            self._steps[name] = {}
            self._dirty[name] = set()
            self._dead[name] = set()

    def _table_rng(self, name):
        # only reached from _init_row under _row_locked's callers, all
        # of which hold self._lock; drop_table's locked pop made the
        # analyzer notice the contrast
        rng = self._rngs.get(name)
        if rng is None:
            import zlib

            rng = np.random.RandomState(
                (self._seed * 1000003 + zlib.crc32(name.encode()))
                % (2 ** 32)
            )
            self._rngs[name] = rng  # edlint: disable=lock-discipline
        return rng

    def _init_row(self, name, dim, scale, kind):
        if kind == "constant":
            return np.full(dim, scale, dtype=np.float32)
        if scale <= 0:
            return np.zeros(dim, dtype=np.float32)
        rng = self._table_rng(name)
        if kind == "uniform":
            return rng.uniform(-scale, scale, size=dim).astype(np.float32)
        if kind == "normal":
            return rng.normal(0.0, scale, size=dim).astype(np.float32)
        # truncated_normal: resample outside [-2*stddev, 2*stddev]
        row = rng.normal(0.0, scale, size=dim)
        bad = np.abs(row) > 2 * scale
        while bad.any():
            row[bad] = rng.normal(0.0, scale, size=int(bad.sum()))
            bad = np.abs(row) > 2 * scale
        return row.astype(np.float32)

    def _row_locked(self, name, id_):
        table = self._tables[name]
        if id_ not in table:
            dim, scale, kind = self._meta[name]
            table[id_] = self._init_row(name, dim, scale, kind)
            n_slots = OPT_SLOT_COUNTS[self._opt[0]]
            self._slots[name][id_] = np.zeros(
                (n_slots, dim), dtype=np.float32
            )
            self._steps[name][id_] = 0
            # a lazy init is a state change the delta chain must carry
            # (same rule as the native get_or_init)
            self._dirty[name].add(id_)
            self._dead[name].discard(id_)
        return table[id_]

    def lookup(self, name, ids):
        if name not in self._meta:
            raise KeyError(name)
        with self._lock:
            return np.stack([
                self._row_locked(name, int(i)).copy() for i in ids
            ])

    def push_gradients(self, name, ids, grads, lr_scale=1.0):
        if name not in self._meta:
            raise KeyError(name)
        opt_type, args = self._opt
        lr = args["lr"] * lr_scale
        ids = np.asarray(ids, dtype=np.int64)
        grads = np.asarray(grads, dtype=np.float32)
        with self._lock:
            if ids.size > 1 and np.unique(ids).size == ids.size:
                # the common shape: clients dedup before pushing, so a
                # push's ids are unique — one vectorized [n, dim]
                # optimizer apply instead of n per-row Python applies
                # (the elementwise math is identical, so results match
                # the sequential path bit for bit)
                self._apply_unique_locked(name, ids, grads, opt_type,
                                          args, lr)
                return
            dirty = self._dirty[name]
            for i, grad in zip(ids, grads):
                i = int(i)
                dirty.add(i)
                w = self._row_locked(name, i)
                slots = self._slots[name][i]
                self._steps[name][i] += 1
                step = self._steps[name][i]
                if opt_type == "sgd":
                    w -= lr * grad
                elif opt_type in ("momentum", "nesterov"):
                    slots[0] = args["momentum"] * slots[0] + grad
                    if opt_type == "nesterov":
                        w -= lr * (grad + args["momentum"] * slots[0])
                    else:
                        w -= lr * slots[0]
                elif opt_type == "adagrad":
                    slots[0] += grad * grad
                    w -= lr * grad / (np.sqrt(slots[0]) + args["epsilon"])
                elif opt_type in ("adam", "amsgrad"):
                    slots[0] = args["beta1"] * slots[0] + (1 - args["beta1"]) * grad
                    slots[1] = (
                        args["beta2"] * slots[1]
                        + (1 - args["beta2"]) * grad * grad
                    )
                    mhat = slots[0] / (1 - args["beta1"] ** step)
                    v = slots[1]
                    if opt_type == "amsgrad":
                        slots[2] = np.maximum(slots[2], v)
                        v = slots[2]
                    vhat = v / (1 - args["beta2"] ** step)
                    w -= lr * mhat / (np.sqrt(vhat) + args["epsilon"])

    def _apply_unique_locked(self, name, ids, grads, opt_type, args, lr):
        """Vectorized optimizer apply for a unique-id push: gather the
        touched rows/slots into dense [n, ...] arrays, run the update
        math once, scatter back. Caller holds the lock and guarantees
        ids are unique (duplicate streams take the sequential path —
        slot-state optimizers are order-sensitive across repeats)."""
        id_list = [int(i) for i in ids]
        self._dirty[name].update(id_list)
        # gather in input order: lazy row init draws from the per-table
        # RNG stream, so creation order must match the sequential path
        rows = [self._row_locked(name, i) for i in id_list]
        w = np.stack(rows)
        slot_map = self._slots[name]
        step_map = self._steps[name]
        steps = np.empty((ids.size, 1), dtype=np.float64)
        for k, i in enumerate(id_list):
            step_map[i] += 1
            steps[k, 0] = step_map[i]
        if opt_type == "sgd":
            w -= lr * grads
        elif opt_type in ("momentum", "nesterov"):
            m = np.stack([slot_map[i][0] for i in id_list])
            m = args["momentum"] * m + grads
            if opt_type == "nesterov":
                w -= lr * (grads + args["momentum"] * m)
            else:
                w -= lr * m
            for k, i in enumerate(id_list):
                slot_map[i][0] = m[k]
        elif opt_type == "adagrad":
            s = np.stack([slot_map[i][0] for i in id_list])
            s += grads * grads
            w -= lr * grads / (np.sqrt(s) + args["epsilon"])
            for k, i in enumerate(id_list):
                slot_map[i][0] = s[k]
        elif opt_type in ("adam", "amsgrad"):
            slots = np.stack([slot_map[i] for i in id_list])
            slots[:, 0] = (
                args["beta1"] * slots[:, 0] + (1 - args["beta1"]) * grads
            )
            slots[:, 1] = (
                args["beta2"] * slots[:, 1]
                + (1 - args["beta2"]) * grads * grads
            )
            # bias corrections in float64 then rounded to float32, the
            # same value the sequential path's weak python-float scalar
            # takes inside its float32 division — keeps this path
            # bit-identical to the per-id loop
            bc1 = (1.0 - args["beta1"] ** steps).astype(np.float32)
            bc2 = (1.0 - args["beta2"] ** steps).astype(np.float32)
            mhat = slots[:, 0] / bc1
            v = slots[:, 1]
            if opt_type == "amsgrad":
                slots[:, 2] = np.maximum(slots[:, 2], v)
                v = slots[:, 2]
            vhat = v / bc2
            w -= lr * mhat / (np.sqrt(vhat) + args["epsilon"])
            for k, i in enumerate(id_list):
                slot_map[i][:] = slots[k]
        for k, row in enumerate(rows):
            row[:] = w[k]

    def drop_rows(self, name, ids):
        """Native-store twin: delete weight row + slots + step count so
        a re-admitted id re-initializes like a never-seen one. Returns
        the number of rows actually dropped."""
        if name not in self._meta:
            raise KeyError(name)
        dropped = 0
        with self._lock:
            table = self._tables[name]
            slots = self._slots[name]
            steps = self._steps[name]
            dirty = self._dirty[name]
            dead = self._dead[name]
            for i in ids:
                i = int(i)
                if table.pop(i, None) is not None:
                    dropped += 1
                    # dirty -> dead: the next delta replays this drop
                    # as a delete so a restore cannot resurrect it
                    dirty.discard(i)
                    dead.add(i)
                slots.pop(i, None)
                steps.pop(i, None)
        return dropped

    def drop_table(self, name):
        if name not in self._meta:
            raise KeyError(name)
        with self._lock:
            self._meta.pop(name, None)
            self._tables.pop(name, None)
            self._slots.pop(name, None)
            self._steps.pop(name, None)
            self._dirty.pop(name, None)
            self._dead.pop(name, None)
            self._rngs.pop(name, None)

    def table_size(self, name):
        return len(self._tables.get(name, {}))

    def bump_version(self):
        with self._lock:
            self.version += 1

    def set_version(self, version):
        """Re-anchor the version clock (checkpoint auto-restore)."""
        with self._lock:
            self.version = int(version)

    def table_names(self):
        return list(self._meta)

    def table_dim(self, name):
        return self._meta[name][0]

    def export_table(self, name):
        with self._lock:
            table = self._tables[name]
            if not table:
                dim = self._meta[name][0]
                return (
                    np.empty((0,), np.int64),
                    np.empty((0, dim), np.float32),
                )
            ids = np.fromiter(table.keys(), dtype=np.int64, count=len(table))
            values = np.stack([table[int(i)] for i in ids])
            return ids, values

    def import_table(self, name, ids, values, shard_id=0, shard_num=0):
        with self._lock:
            dirty = self._dirty[name]
            for i, row in zip(ids, values):
                i = int(i)
                if shard_num > 0 and i % shard_num != shard_id:
                    continue
                self._row_locked(name, i)[:] = row
                dirty.add(i)

    @property
    def opt_type(self):
        return self._opt[0]

    def table_slots(self, name):
        if name not in self._meta:
            raise KeyError(name)
        return OPT_SLOT_COUNTS[self._opt[0]]

    def export_table_full(self, name):
        with self._lock:
            table = self._tables[name]
            dim = self._meta[name][0]
            slots = self.table_slots(name)
            row_floats = dim * (1 + slots)
            if not table:
                return (
                    np.empty((0,), np.int64),
                    np.empty((0, row_floats), np.float32),
                    np.empty((0,), np.int64),
                )
            ids = np.fromiter(table.keys(), dtype=np.int64, count=len(table))
            rows = np.stack([
                np.concatenate(
                    [table[int(i)]] + list(self._slots[name][int(i)])
                )
                for i in ids
            ])
            steps = np.asarray(
                [self._steps[name][int(i)] for i in ids], np.int64
            )
            return ids, rows, steps

    def import_table_full(self, name, ids, rows, steps,
                          shard_id=0, shard_num=0):
        dim = self._meta[name][0]
        slots = self.table_slots(name)
        rows = np.asarray(rows, np.float32)
        exact = rows.ndim == 2 and rows.shape[1] == dim * (1 + slots)
        with self._lock:
            dirty = self._dirty[name]
            for idx, i in enumerate(ids):
                i = int(i)
                if shard_num > 0 and i % shard_num != shard_id:
                    continue
                self._row_locked(name, i)[:] = rows[idx][:dim]
                dirty.add(i)
                if exact:
                    self._slots[name][i][:] = rows[idx][dim:].reshape(
                        slots, dim
                    )
                    self._steps[name][i] = int(steps[idx])

    def dirty_count(self, name):
        """Rows a delta export would currently carry (gauge/sizing)."""
        if name not in self._meta:
            raise KeyError(name)
        with self._lock:
            return len(self._dirty[name])

    def export_table_dirty(self, name, clear=True):
        """Native-store twin of the delta-checkpoint primitive: under
        the store lock, export every dirty row's full train state (ids
        ascending — deterministic files, never set order) plus the
        dead-id tombstones, then clear both sets. Returns ``(ids,
        rows, steps, dead_ids)``; bit-exact with the native export."""
        if name not in self._meta:
            raise KeyError(name)
        with self._lock:
            dim = self._meta[name][0]
            slots = self.table_slots(name)
            row_floats = dim * (1 + slots)
            dirty = sorted(self._dirty[name])
            dead = np.asarray(sorted(self._dead[name]), np.int64)
            if dirty:
                ids = np.asarray(dirty, np.int64)
                table = self._tables[name]
                rows = np.stack([
                    np.concatenate(
                        [table[i]] + list(self._slots[name][i])
                    )
                    for i in dirty
                ]).astype(np.float32, copy=False)
                steps = np.asarray(
                    [self._steps[name][i] for i in dirty], np.int64
                )
            else:
                ids = np.empty((0,), np.int64)
                rows = np.empty((0, row_floats), np.float32)
                steps = np.empty((0,), np.int64)
            if clear:
                self._dirty[name] = set()
                self._dead[name] = set()
            return ids, rows, steps, dead

    def clear_dirty(self, name):
        """Drop all dirty/dead bookkeeping (taken before a full base
        export: the base carries complete state)."""
        if name not in self._meta:
            raise KeyError(name)
        with self._lock:
            self._dirty[name] = set()
            self._dead[name] = set()


def create_store(seed=0, prefer_native=True):
    if prefer_native and native_lib() is not None:
        return NativeEmbeddingStore(seed=seed)
    return NumpyEmbeddingStore(seed=seed)
