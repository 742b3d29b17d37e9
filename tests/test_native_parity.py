"""Native data plane (ISSUE 11): bit-exact parity + loader hardening.

The contract under test: the native store's wire-blob fast paths
(``push_gradients_blob`` / ``lookup_blob`` / ``import_blob``) are
BIT-IDENTICAL to the numpy pipeline they replace — across every sparse
optimizer (incl. the nesterov/amsgrad variants), every wire dtype
(fp32 / bf16 / fp16), and duplicate-heavy id streams — and a
checkpoint written by either backend restores bit-exactly into the
other, down to optimizer slot values and per-row adam step counts.
"""

import numpy as np
import pytest

from elasticdl_tpu.common.tensor_utils import (
    blob_to_ndarray,
    deduplicate_indexed_slices,
    pack_ids,
    serialize_indexed_slices,
    unpack_ids,
)
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.ps.checkpoint import SparseCheckpointSaver
from elasticdl_tpu.ps.embedding_store import (
    NativeEmbeddingStore,
    NumpyEmbeddingStore,
    native_lib,
)
from elasticdl_tpu.ps.servicer import PserverServicer

needs_native = pytest.mark.skipif(
    native_lib() is None, reason="native store unavailable"
)

ALL_OPTS = ("sgd", "momentum", "nesterov", "adagrad", "adam", "amsgrad")
WIRE_DTYPES = ("float32", "bfloat16", "float16")


def _wire_np_dtype(name):
    if name == "float32":
        return None  # bit-exact fp32 payload (no downcast)
    if name == "bfloat16":
        import ml_dtypes

        return np.dtype(ml_dtypes.bfloat16)
    return np.dtype(np.float16)


def _paired_stores(opt, dim=8, lr=0.013):
    """Native + numpy twins with deterministic (constant) row init so
    lazy materialization during pushes cannot diverge via RNG."""
    native = NativeEmbeddingStore(seed=3)
    ref = NumpyEmbeddingStore(seed=3)
    for store in (native, ref):
        store.set_optimizer(opt, lr=lr)
        store.create_table("t", dim, init_scale=0.37,
                           initializer="constant")
    return native, ref


def _assert_tables_bit_equal(a, b, name="t"):
    ia, ra, sa = a.export_table_full(name)
    ib, rb, sb = b.export_table_full(name)
    oa, ob = np.argsort(ia), np.argsort(ib)
    np.testing.assert_array_equal(ia[oa], ib[ob])
    # exact: weights AND optimizer slot columns, no tolerance
    np.testing.assert_array_equal(ra[oa], rb[ob])
    np.testing.assert_array_equal(sa[oa], sb[ob])


# ---------------------------------------------------------------------------
# apply parity: native blob call vs numpy deserialize+dedup+apply


@needs_native
@pytest.mark.parametrize("wire", WIRE_DTYPES)
@pytest.mark.parametrize("opt", ALL_OPTS)
def test_blob_apply_bit_identical_duplicate_stream(opt, wire):
    import zlib

    # stable per-combo seed: hash() is salted per process, which would
    # make a rare-input parity failure irreproducible across runs
    rng = np.random.RandomState(zlib.crc32((opt + wire).encode()))
    native, ref = _paired_stores(opt)
    dt = _wire_np_dtype(wire)
    for _ in range(5):
        # duplicate-heavy: ~95% duplicate rate, the Zipfian CTR shape
        ids = rng.randint(0, 30, size=600).astype(np.int64)
        grads = rng.randn(600, 8).astype(np.float32)
        slices = serialize_indexed_slices(grads, ids, wire_dtype=dt)
        native.push_gradients_blob(
            "t", unpack_ids(slices), slices.concat_tensors.content,
            slices.concat_tensors.dtype, lr_scale=0.7,
        )
        values, rids = blob_to_ndarray(slices.concat_tensors), \
            unpack_ids(slices)
        if values.dtype != np.float32:
            values = values.astype(np.float32)
        values, rids = deduplicate_indexed_slices(values, rids)
        ref.push_gradients("t", rids, values, lr_scale=0.7)
    _assert_tables_bit_equal(native, ref)


@needs_native
@pytest.mark.parametrize("opt", ("sgd", "adam"))
def test_blob_apply_bit_identical_unique_stream(opt):
    rng = np.random.RandomState(9)
    native, ref = _paired_stores(opt)
    for _ in range(4):
        ids = rng.permutation(500)[:128].astype(np.int64)
        grads = rng.randn(128, 8).astype(np.float32)
        slices = serialize_indexed_slices(grads, ids)
        native.push_gradients_blob(
            "t", unpack_ids(slices), slices.concat_tensors.content,
            slices.concat_tensors.dtype,
        )
        values, rids = deduplicate_indexed_slices(grads, ids)
        ref.push_gradients("t", rids, values)
    _assert_tables_bit_equal(native, ref)


@needs_native
def test_blob_apply_validates_payload_shape():
    native, _ = _paired_stores("sgd")
    ids = np.arange(4, dtype=np.int64)
    with pytest.raises(ValueError, match="payload bytes"):
        native.push_gradients_blob("t", ids, b"\x00" * 12, "float32")


# ---------------------------------------------------------------------------
# wire dtype conversions: exhaustive, both directions


@needs_native
def test_f16_and_bf16_upcast_exhaustive():
    """Every finite 16-bit pattern decodes to the exact same fp32 bits
    numpy's astype produces (incl. subnormals)."""
    import ml_dtypes

    patterns = np.arange(65536, dtype=np.uint16)
    for name, np_dt in (("float16", np.float16),
                        ("bfloat16", ml_dtypes.bfloat16)):
        as16 = patterns.view(np_dt)
        want = as16.astype(np.float32)
        finite = np.isfinite(want)
        store = NativeEmbeddingStore(seed=0)
        store.set_optimizer("sgd", lr=1.0)
        store.create_table("t", 8, init_scale=0.0, initializer="constant")
        ids = np.arange(65536 // 8, dtype=np.int64)
        store.import_blob("t", ids, as16.tobytes(), name)
        got = store.lookup("t", ids).reshape(-1)
        np.testing.assert_array_equal(
            got.view(np.uint32)[finite], want.view(np.uint32)[finite]
        )


@needs_native
def test_wire_downcast_matches_numpy_astype():
    """lookup_blob's in-C downcast (RNE) == numpy astype, including
    f16 subnormal results and overflow-to-inf."""
    import ml_dtypes

    rng = np.random.RandomState(7)
    with np.errstate(over="ignore"):
        vals = np.concatenate([
            rng.randn(4096).astype(np.float32),
            (rng.randn(2048) * 1e-7).astype(np.float32),   # f16 subnormal
            (rng.randn(2048) * 1e5).astype(np.float32),    # f16 overflow
            (rng.randn(2048) * 1e38).astype(np.float32),
        ]).reshape(-1, 8)
    store = NativeEmbeddingStore(seed=0)
    store.set_optimizer("sgd", lr=1.0)
    store.create_table("t", 8, init_scale=0.0, initializer="constant")
    ids = np.arange(vals.shape[0], dtype=np.int64)
    store.import_table("t", ids, vals)
    for name, np_dt in (("bfloat16", ml_dtypes.bfloat16),
                        ("float16", np.float16)):
        content, dtype_name = store.lookup_blob("t", ids, name)
        assert dtype_name == name
        with np.errstate(over="ignore"):
            want = vals.astype(np_dt).reshape(-1).view(np.uint16)
        got = np.frombuffer(content, dtype=np.uint16)
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# servicer-level parity: identical requests, bit-identical state


def _servicer_with(store_cls, opt="adam"):
    store = store_cls(seed=5)
    store.set_optimizer(opt, lr=0.01)
    servicer = PserverServicer(store, use_async=True)
    infos = pb.Model()
    for name in ("a", "b", "c"):
        infos.embedding_table_infos.add(
            name=name, dim=8, initializer="constant:0.2"
        )
    servicer.push_model(infos)
    return store, servicer


@needs_native
@pytest.mark.parametrize("apply_threads", ["1", "4"])
def test_servicer_async_push_pull_parity(apply_threads, monkeypatch):
    """The full async RPC surface — multi-table pushes (packed blobs)
    then pulls — bit-matches across backends, with and without the
    EDL_PS_APPLY_THREADS fan-out."""
    monkeypatch.setenv("EDL_PS_APPLY_THREADS", apply_threads)
    rng = np.random.RandomState(0)
    pushes = []
    for step in range(4):
        request = pb.PushGradientsRequest()
        request.gradients.version = step
        for name in ("a", "b", "c"):
            ids = rng.randint(0, 50, size=300).astype(np.int64)
            grads = rng.randn(300, 8).astype(np.float32)
            serialize_indexed_slices(
                grads, ids, request.gradients.embedding_tables[name]
            )
        pushes.append(request)
    results = {}
    for cls in (NativeEmbeddingStore, NumpyEmbeddingStore):
        store, servicer = _servicer_with(cls)
        for request in pushes:
            assert servicer.push_gradients(request).accepted
        pull = pb.PullEmbeddingVectorsRequest(
            name="a", ids_blob=pack_ids(np.arange(50))
        )
        results[cls] = (store, servicer.pull_embedding_vectors(pull))
    native_blob = results[NativeEmbeddingStore][1]
    numpy_blob = results[NumpyEmbeddingStore][1]
    assert native_blob.dtype == numpy_blob.dtype
    assert list(native_blob.dims) == list(numpy_blob.dims)
    assert native_blob.content == numpy_blob.content
    for name in ("a", "b", "c"):
        _assert_tables_bit_equal(
            results[NativeEmbeddingStore][0],
            results[NumpyEmbeddingStore][0],
            name,
        )


@needs_native
def test_servicer_wire_dtype_pull_parity(monkeypatch):
    monkeypatch.setenv("EDL_WIRE_DTYPE", "bfloat16")
    blobs = {}
    for cls in (NativeEmbeddingStore, NumpyEmbeddingStore):
        _, servicer = _servicer_with(cls)
        pull = pb.PullEmbeddingVectorsRequest(
            name="a", ids_blob=pack_ids(np.arange(20))
        )
        blobs[cls] = servicer.pull_embedding_vectors(pull)
    assert blobs[NativeEmbeddingStore].dtype == "bfloat16"
    assert (
        blobs[NativeEmbeddingStore].content
        == blobs[NumpyEmbeddingStore].content
    )


@needs_native
def test_servicer_row_import_parity():
    """push_embedding_rows (device-tier writeback) through the native
    import_blob fast path == the numpy import, incl. duplicate ids
    resolving last-write-wins."""
    rng = np.random.RandomState(2)
    ids = np.array([5, 9, 5, 7, 9], dtype=np.int64)  # dup: last wins
    values = rng.randn(5, 8).astype(np.float32)
    request = pb.Model()
    serialize_indexed_slices(values, ids, request.embedding_tables["a"])
    stores = {}
    for cls in (NativeEmbeddingStore, NumpyEmbeddingStore):
        store, servicer = _servicer_with(cls)
        response = servicer.push_embedding_rows(request)
        assert response.accepted
        stores[cls] = store
    for store in stores.values():
        got = store.lookup("a", np.array([5, 9, 7], dtype=np.int64))
        np.testing.assert_array_equal(got[0], values[2])
        np.testing.assert_array_equal(got[1], values[4])
        np.testing.assert_array_equal(got[2], values[3])


@needs_native
def test_servicer_legacy_repeated_ids_still_served():
    """A pre-ids_blob push (repeated ids, no packed blob) must route
    through the numpy fallback and still apply — on both backends."""
    grads = np.ones((3, 8), dtype=np.float32)
    request = pb.PushGradientsRequest()
    slices = request.gradients.embedding_tables["a"]
    serialize_indexed_slices(grads, [1, 2, 1], slices, packed=False)
    assert not slices.ids_blob and list(slices.ids) == [1, 2, 1]
    stores = {}
    for cls in (NativeEmbeddingStore, NumpyEmbeddingStore):
        store, servicer = _servicer_with(cls, opt="sgd")
        assert servicer.push_gradients(request).accepted
        stores[cls] = store
    _assert_tables_bit_equal(
        stores[NativeEmbeddingStore], stores[NumpyEmbeddingStore], "a"
    )
    # duplicate id 1 was summed (dedup-then-apply semantics)
    row = stores[NumpyEmbeddingStore].lookup(
        "a", np.array([1], dtype=np.int64)
    )[0]
    expected = np.float32(0.2) - np.float32(0.01) * np.float32(2.0)
    np.testing.assert_array_equal(row, np.full(8, expected))


# ---------------------------------------------------------------------------
# checkpoint interop: either backend restores the other bit-exactly


@needs_native
@pytest.mark.parametrize("opt", ("adam", "amsgrad", "nesterov"))
@pytest.mark.parametrize(
    "writer_cls,reader_cls",
    [
        (NativeEmbeddingStore, NumpyEmbeddingStore),
        (NumpyEmbeddingStore, NativeEmbeddingStore),
    ],
)
def test_checkpoint_interop_bit_exact(tmp_path, writer_cls, reader_cls,
                                      opt):
    rng = np.random.RandomState(4)
    writer = writer_cls(seed=1)
    writer.set_optimizer(opt, lr=0.02)
    writer.create_table("t", 6, init_scale=0.1, initializer="constant")
    for _ in range(5):
        ids = rng.randint(0, 40, size=90).astype(np.int64)
        grads = rng.randn(90, 6).astype(np.float32)
        values, uids = deduplicate_indexed_slices(grads, ids)
        writer.push_gradients("t", uids, values)
    saver = SparseCheckpointSaver(str(tmp_path))
    saver.save(7, writer)

    reader = reader_cls(seed=99)  # different seed: state must come
    reader.set_optimizer(opt, lr=0.02)  # from the checkpoint alone
    restored = SparseCheckpointSaver(str(tmp_path)).restore(reader)
    assert restored == 7
    # weights, slot values AND adam step counts survive the crossing
    _assert_tables_bit_equal(writer, reader)
    # and training CONTINUES identically from the restored state
    ids = np.arange(10, dtype=np.int64)
    grads = rng.randn(10, 6).astype(np.float32)
    writer.push_gradients("t", ids, grads)
    reader.push_gradients("t", ids, grads)
    _assert_tables_bit_equal(writer, reader)


# ---------------------------------------------------------------------------
# loader hardening: failures degrade to numpy, never raise


def test_create_store_falls_back_when_native_missing(monkeypatch):
    from elasticdl_tpu.ps import embedding_store as mod

    monkeypatch.setattr(mod, "native_lib", lambda: None)
    store = mod.create_store(prefer_native=True)
    assert isinstance(store, NumpyEmbeddingStore)


def test_load_native_corrupt_so_returns_none(tmp_path, monkeypatch):
    """A present-but-unloadable .so (truncated build, wrong arch) must
    log-and-fall-back, not raise mid-job."""
    from elasticdl_tpu.ps import embedding_store as mod

    bogus = tmp_path / "libedl_embedding.so"
    bogus.write_bytes(b"not an ELF file")
    monkeypatch.setattr(mod, "_SO_PATH", str(bogus))
    assert mod._load_native() is None


def test_load_native_abi_drift_detected(monkeypatch, tmp_path):
    """A loadable library missing the ABI symbol (or reporting a
    different clock) is treated as stale: one rebuild attempt, then
    numpy fallback — never a call through a drifted ABI."""
    from elasticdl_tpu.ps import embedding_store as mod

    class _NoAbiLib:
        def __getattr__(self, name):
            raise AttributeError(name)

    assert mod._abi_of(_NoAbiLib()) is None

    class _OldAbi:
        class _Fn:
            restype = None
            argtypes = None

            def __call__(self):
                return 1

        edl_store_abi_version = _Fn()

    assert mod._abi_of(_OldAbi()) == 1
    # end to end: loading a valid-but-ancient .so path falls back when
    # the rebuild cannot produce the expected ABI
    bogus = tmp_path / "libedl_embedding.so"
    bogus.write_bytes(b"junk")
    monkeypatch.setattr(mod, "_SO_PATH", str(bogus))
    monkeypatch.setattr(
        mod, "_build_native",
        lambda force=False: (_ for _ in ()).throw(RuntimeError("no cc")),
    )
    assert mod._load_native() is None


@needs_native
def test_abi_version_matches_binding():
    from elasticdl_tpu.ps import embedding_store as mod

    assert mod._abi_of(native_lib()) == mod._EXPECTED_ABI


@needs_native
def test_cdll_fresh_bypasses_stale_mapping():
    """dlopen dedups by pathname: a plain re-CDLL of _SO_PATH after a
    rebuild returns the already-mapped (stale) library. _cdll_fresh
    must produce a genuinely new mapping with live symbols."""
    import ctypes

    from elasticdl_tpu.ps import embedding_store as mod

    stale = ctypes.CDLL(mod._SO_PATH)
    fresh = mod._cdll_fresh(mod._SO_PATH)
    assert fresh._handle != stale._handle
    assert mod._abi_of(fresh) == mod._EXPECTED_ABI


@needs_native
def test_abi_drift_recovery_reloads_rebuilt_library(monkeypatch):
    """The drift branch end to end, SUCCESS side: first load reports a
    stale ABI, the forced rebuild runs once, and the fresh-copy reload
    passes the re-check — the loader returns a live native lib instead
    of silently falling back to numpy."""
    from elasticdl_tpu.ps import embedding_store as mod

    real_abi_of = mod._abi_of
    loads = []

    def fake_abi(lib):
        loads.append(lib)
        if len(loads) == 1:
            return 1  # the stale first mapping
        return real_abi_of(lib)

    built = []
    monkeypatch.setattr(mod, "_abi_of", fake_abi)
    monkeypatch.setattr(
        mod, "_build_native", lambda force=False: built.append(force)
    )
    lib = mod._load_native_checked()
    assert lib is not None
    # the routine make every load runs (a no-op when the .so is
    # current), then exactly one forced rebuild for the drift
    assert built == [False, True]
    assert len(loads) == 2  # stale load + fresh reload
    assert loads[0]._handle != loads[1]._handle


# ---------------------------------------------------------------------------
# the existing store-level suite keeps covering the classic (non-blob)
# API; this sanity check pins that the old parity test's tolerance is
# now achievable exactly


@needs_native
def test_classic_push_api_now_bit_exact():
    native, ref = _paired_stores("adam")
    rng = np.random.RandomState(1)
    init = rng.rand(3, 8).astype(np.float32)
    ids = np.array([1, 2, 3], dtype=np.int64)
    native.import_table("t", ids, init)
    ref.import_table("t", ids, init)
    for _ in range(5):
        grads = rng.randn(3, 8).astype(np.float32)
        native.push_gradients("t", ids, grads, lr_scale=1.0 / 3.0)
        ref.push_gradients("t", ids, grads, lr_scale=1.0 / 3.0)
    np.testing.assert_array_equal(
        native.lookup("t", ids), ref.lookup("t", ids)
    )
