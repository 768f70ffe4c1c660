"""Native libsvm parser: byte-for-byte equivalence with the Python parser
and a throughput sanity check."""

import numpy as np
import pytest

from difacto_tpu.data.parsers import parse_libsvm
from difacto_tpu.data.native_parsers import parse_libsvm_native
from difacto_tpu.native import get_lib

needs_native = pytest.mark.skipif(get_lib() is None,
                                  reason="native toolchain unavailable")


@needs_native
def test_native_matches_python_on_fixture(rcv1_path):
    chunk = open(rcv1_path, "rb").read()
    a = parse_libsvm(chunk)
    b = parse_libsvm_native(chunk)
    assert a.size == b.size == 100
    np.testing.assert_array_equal(a.offset, b.offset)
    np.testing.assert_array_equal(a.label, b.label)
    np.testing.assert_array_equal(a.index, b.index)
    np.testing.assert_allclose(a.values_or_ones(), b.values_or_ones(),
                               rtol=1e-6)


@needs_native
def test_native_edge_cases():
    # empty chunk, blank lines, no-feature rows, binary values, \r\n
    cases = [
        b"",
        b"\n\n\n",
        b"1\n0\n",                       # label-only rows
        b"1 5:1 7:1\n0 2:1\n",           # all-ones -> value elided
        b"-1 3:0.5 9:2.25\r\n+1 1:1e-3\r\n",
        b"0.5 18446744073709551615:4\n",  # uint64 max feature id
    ]
    for chunk in cases:
        a = parse_libsvm(chunk)
        b = parse_libsvm_native(chunk)
        assert a.size == b.size, chunk
        np.testing.assert_array_equal(a.offset, b.offset)
        np.testing.assert_array_equal(a.label, b.label)
        np.testing.assert_array_equal(a.index, b.index)
        np.testing.assert_allclose(a.values_or_ones(), b.values_or_ones(),
                                   rtol=1e-6, err_msg=str(chunk))
    # binary elision: all values 1 -> value is None
    assert parse_libsvm_native(b"1 5:1 7:1\n").value is None
    assert parse_libsvm_native(b"1 5:2\n").value is not None


@needs_native
def test_native_rejects_malformed():
    with pytest.raises(ValueError):
        parse_libsvm_native(b"1 nocolon\n")
    # empty value must not swallow the next line's label (strtof skips \n)
    with pytest.raises(ValueError):
        parse_libsvm_native(b"1 5:\n0 3:1\n")
    # negative index must not wrap to a huge uint64
    with pytest.raises(ValueError):
        parse_libsvm_native(b"1 -5:2\n")
    # exotic whitespace after ':' must not swallow the next line either
    with pytest.raises(ValueError):
        parse_libsvm_native(b"1 5:\x0c\n0 3:1\n")
    # id one past uint64 max must error, not clamp
    with pytest.raises(ValueError):
        parse_libsvm_native(b"1 18446744073709551616:1\n")


@needs_native
def test_native_is_faster(rcv1_path):
    import time
    chunk = open(rcv1_path, "rb").read() * 50  # ~5000 rows

    def best_of(f, n=3):
        times = []
        for _ in range(n):
            t0 = time.perf_counter()
            f(chunk)
            times.append(time.perf_counter() - t0)
        return min(times)

    py = best_of(parse_libsvm)
    native = best_of(parse_libsvm_native)
    # typically 10-30x faster; generous bound to stay robust under CI load
    assert native < py * 0.8, (native, py)


@needs_native
def test_reader_uses_native(rcv1_path):
    """End to end: the Reader path produces the same 100 rows."""
    from difacto_tpu.data import Reader
    blocks = list(Reader(rcv1_path, "libsvm"))
    assert sum(b.size for b in blocks) == 100


@needs_native
def test_murmur64a_native_matches_python():
    """The C++ and pure-Python MurmurHash64A must agree bit for bit —
    hosts with and without the toolchain must build the same feature
    space (parsers.py _hash64 docstring contract)."""
    import ctypes
    from difacto_tpu.data.parsers import _hash64
    lib = get_lib()
    for s in [b"", b"a", b"ab", b"criteo", b"x" * 7, b"y" * 8, b"z" * 9,
              b"longer_categorical_value" * 3, bytes(range(256))]:
        assert _hash64(s) == lib.difacto_murmur64a(s, len(s), 0), s


def _criteo_chunk(nrows, with_empties=True, seed=0):
    rng = np.random.RandomState(seed)
    lines = []
    for _ in range(nrows):
        ints = [str(rng.randint(0, 1000))
                if (not with_empties or rng.rand() > 0.2) else ""
                for _ in range(13)]
        cats = [f"c{rng.randint(0, 9999):x}"
                if (not with_empties or rng.rand() > 0.1) else ""
                for _ in range(26)]
        lines.append(f"{rng.randint(0, 2)}\t" + "\t".join(ints + cats))
    return ("\n".join(lines) + "\n").encode()


@needs_native
def test_criteo_native_matches_python():
    from difacto_tpu.data.parsers import parse_criteo
    from difacto_tpu.data.native_parsers import parse_criteo_native
    chunk = _criteo_chunk(300)
    a = parse_criteo(chunk)
    b = parse_criteo_native(chunk)
    assert a.size == b.size == 300
    np.testing.assert_array_equal(a.offset, b.offset)
    np.testing.assert_array_equal(a.label, b.label)
    np.testing.assert_array_equal(a.index, b.index)


@needs_native
def test_criteo_native_test_mode_and_crlf():
    """is_train=False (label-less rows; regression: buffer sizing) and
    CRLF blank lines (regression: phantom rows) match the Python parser."""
    from difacto_tpu.data.parsers import parse_criteo
    from difacto_tpu.data.native_parsers import parse_criteo_native
    # fully-populated label-less rows — the worst case for nnz sizing
    rng = np.random.RandomState(1)
    lines = ["\t".join(str(rng.randint(0, 99)) for _ in range(39))
             for _ in range(8)]
    chunk = ("\n".join(lines) + "\n").encode()
    a = parse_criteo(chunk, is_train=False)
    b = parse_criteo_native(chunk, is_train=False)
    assert a.size == b.size == 8
    np.testing.assert_array_equal(a.index, b.index)
    assert (b.label == 0).all()

    crlf = b"1\ta\tb\r\n\r\n0\tc\r\n"
    a = parse_criteo(crlf)
    b = parse_criteo_native(crlf)
    assert a.size == b.size == 2  # the blank CRLF line is not a row
    np.testing.assert_array_equal(a.label, b.label)
    np.testing.assert_array_equal(a.index, b.index)


@needs_native
def test_adfea_native_matches_python():
    from difacto_tpu.data.native_parsers import parse_adfea_native
    from difacto_tpu.data.parsers import parse_adfea
    rng = np.random.RandomState(3)
    lines = []
    for i in range(200):
        feats = " ".join(f"{rng.randint(0, 1 << 40)}:{rng.randint(0, 9000)}"
                         for _ in range(rng.randint(1, 12)))
        lines.append(f"{i} {rng.randint(1, 5)} {rng.randint(0, 2)} {feats}")
    chunk = ("\n".join(lines) + "\n").encode()
    a = parse_adfea(chunk)
    b = parse_adfea_native(chunk)
    assert a.size == b.size == 200
    np.testing.assert_array_equal(a.offset, b.offset)
    np.testing.assert_array_equal(a.label, b.label)
    np.testing.assert_array_equal(a.index, b.index)
    assert a.value is None and b.value is None

    # space-only separators (single line, the max_rows sizing edge) and
    # tab separators
    flat = (" ".join(lines[:50])).encode()
    a, b = parse_adfea(flat), parse_adfea_native(flat)
    assert a.size == b.size == 50
    np.testing.assert_array_equal(a.index, b.index)
    tabbed = chunk.replace(b" ", b"\t")
    a, b = parse_adfea(tabbed), parse_adfea_native(tabbed)
    np.testing.assert_array_equal(a.offset, b.offset)


@needs_native
def test_library_is_keyed_on_source_bytes(tmp_path, monkeypatch):
    """A library built from other sources must never be loaded: the
    file name carries a hash of the three .cc files (mtimes prove
    nothing — a copied tree resets them)."""
    import os
    import shutil

    from difacto_tpu import native

    srcs = []
    for s in native._SRC:
        srcs.append(str(tmp_path / os.path.basename(s)))
        shutil.copy(s, srcs[-1])
    assert native.lib_path(srcs) == native.lib_path()
    with open(srcs[1], "ab") as f:
        f.write(b"\n")
    assert native.lib_path(srcs) != native.lib_path()

    # a stale library under the pre-hash name is not even looked at
    stale = os.path.join(os.path.dirname(native.lib_path()),
                         "_difacto_native.so")
    with open(stale, "wb") as f:
        f.write(b"not a shared object")
    try:
        monkeypatch.setattr(native, "_lib", None)
        monkeypatch.setattr(native, "_tried", False)
        lib = native.get_lib()
        assert lib is not None and lib._name == native.lib_path()
    finally:
        os.unlink(stale)
