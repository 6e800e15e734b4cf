"""C++ EDLR reader vs the Python implementation (same file, same bytes)."""

import os
import subprocess

import pytest

from elasticdl_tpu.data.recordio import (
    RecordIOReader,
    RecordIOWriter,
    open_recordio,
)
from elasticdl_tpu.native import NativeRecordIOReader, native_lib


def _ensure_built():
    """Build the library where a fresh checkout has none, then load it.
    Every xdist worker collects this module at once: ``build`` puts the
    library in place whole (os.replace), so whichever worker finds it
    can load it, and the load is tried again only after this process's
    own build has returned."""
    import elasticdl_tpu.native as native_mod
    from elasticdl_tpu.native import build

    if native_lib() is None:
        try:
            build.build(verbose=False)
        except (OSError, subprocess.CalledProcessError):
            return False
        # forget the load that failed before the library was there
        native_mod._load_failed = False
        native_mod._handle = None
    return native_lib() is not None


pytestmark = pytest.mark.skipif(
    not _ensure_built(), reason="native toolchain unavailable"
)


def _write(tmp_path, records):
    path = str(tmp_path / "data.edlr")
    with RecordIOWriter(path) as w:
        for r in records:
            w.write(r)
    return path


def test_native_matches_python(tmp_path):
    records = [b"alpha", b"", b"x" * 10000, b"tail"]
    path = _write(tmp_path, records)
    with NativeRecordIOReader(path) as native, RecordIOReader(path) as py:
        assert len(native) == len(py) == 4
        for i in range(4):
            assert native.read(i) == bytes(py.read(i)) == records[i]
        assert list(native.read_range(1, 3)) == records[1:3]


def test_native_crc_validation(tmp_path):
    path = _write(tmp_path, [b"payload"])
    with NativeRecordIOReader(path) as r:
        assert r.read(0, validate=True) == b"payload"
    # corrupt the payload in place
    with open(path, "r+b") as f:
        f.seek(8 + 8)  # header + record header
        f.write(b"X")
    with NativeRecordIOReader(path) as r:
        with pytest.raises(ValueError):
            r.read(0, validate=True)


def test_native_rejects_garbage(tmp_path):
    bad = str(tmp_path / "bad.bin")
    with open(bad, "wb") as f:
        f.write(b"not an edlr file at all........")
    with pytest.raises(ValueError):
        NativeRecordIOReader(bad)


def test_factory_prefers_native(tmp_path):
    path = _write(tmp_path, [b"a"])
    reader = open_recordio(path)
    assert isinstance(reader, NativeRecordIOReader)
    reader.close()


def _patch(path, offset, value_u64):
    import struct

    with open(path, "r+b") as f:
        f.seek(offset)
        f.write(struct.pack("<Q", value_u64))


def test_native_rejects_wrapping_index_offset(tmp_path):
    # index_offset chosen so index_offset + 8 wraps past 2**64 and the
    # additive bounds check would accept it
    path = _write(tmp_path, [b"abc"])
    size = os.path.getsize(path)
    _patch(path, size - 12, 2**64 - 4)
    with pytest.raises(ValueError):
        NativeRecordIOReader(path)


def test_native_rejects_wrapping_record_count(tmp_path):
    # count * 8 == 0 mod 2**64: additive check would pass, reader would
    # then index 2**61 "records" off the end of the mapping
    path = _write(tmp_path, [b"abc"])
    size = os.path.getsize(path)
    import struct

    with open(path, "rb") as f:
        f.seek(size - 12)
        index_offset = struct.unpack("<Q", f.read(8))[0]
    _patch(path, index_offset, 2**61)
    with pytest.raises(ValueError):
        NativeRecordIOReader(path)


def test_native_rejects_wrapping_record_offset(tmp_path):
    # offsets[0] near 2**64: off + header wraps, payload_len check would
    # read out of the mapping without the subtraction-form bounds
    path = _write(tmp_path, [b"abc"])
    size = os.path.getsize(path)
    import struct

    with open(path, "rb") as f:
        f.seek(size - 12)
        index_offset = struct.unpack("<Q", f.read(8))[0]
    _patch(path, index_offset + 8, 2**64 - 2)
    with NativeRecordIOReader(path) as r:
        with pytest.raises(IndexError):
            r.read(0)


def test_native_writer_round_trip(tmp_path):
    """Native writer -> both readers; byte-identical layout to the
    Python writer for the same records."""
    pytest.importorskip("ctypes")
    from elasticdl_tpu.data.recordio import RecordIOReader, RecordIOWriter
    from elasticdl_tpu.native import (
        NativeRecordIOReader,
        NativeRecordIOWriter,
        native_lib,
    )

    if native_lib() is None:
        pytest.skip("native library not built")

    records = [b"alpha", b"", b"\x00\x01\x02" * 100, b"tail"]
    native_path = str(tmp_path / "native.edlr")
    with NativeRecordIOWriter(native_path) as w:
        for r in records:
            w.write(r)
        assert w.num_records == len(records)

    python_path = str(tmp_path / "python.edlr")
    with RecordIOWriter(python_path) as w:
        for r in records:
            w.write(r)

    # identical bytes: one format, two implementations
    assert (
        open(native_path, "rb").read() == open(python_path, "rb").read()
    )

    for reader_cls in (RecordIOReader, NativeRecordIOReader):
        r = reader_cls(native_path)
        assert len(r) == len(records)
        got = [bytes(r.read(i, validate=True)) for i in range(len(r))]
        assert got == records
        r.close()


def test_native_writer_abort_leaves_rejectable_file(tmp_path):
    """An exception mid-write must NOT finalize: the tail-less file is
    rejected by both readers instead of serving a partial index."""
    from elasticdl_tpu.data.recordio import RecordIOReader
    from elasticdl_tpu.native import NativeRecordIOWriter, native_lib

    if native_lib() is None:
        pytest.skip("native library not built")

    path = str(tmp_path / "torn.edlr")
    with pytest.raises(RuntimeError):
        with NativeRecordIOWriter(path) as w:
            w.write(b"only record")
            raise RuntimeError("boom")
    with pytest.raises(ValueError):
        RecordIOReader(path)


def test_create_recordio_factory(tmp_path):
    from elasticdl_tpu.data.recordio import create_recordio, open_recordio
    from elasticdl_tpu.native import native_lib

    path = str(tmp_path / "f.edlr")
    with create_recordio(path) as w:
        w.write(b"one")
        w.write(b"two")
    r = open_recordio(path)
    assert [bytes(r.read(i)) for i in range(len(r))] == [b"one", b"two"]
    r.close()
    if native_lib() is not None:
        from elasticdl_tpu.native import NativeRecordIOWriter

        assert isinstance(create_recordio(path + "2"), NativeRecordIOWriter)
