"""Native C++ graph builder vs the NumPy fallback (parity + robustness)."""

import os

import numpy as np
import pytest

from graphmine_tpu.io import native
from graphmine_tpu.io.edges import load_edge_list

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module", autouse=True)
def built_lib():
    assert native.available()  # builds native/libgraphbuild.so on first use


@pytest.mark.parametrize("source_ok", [True, False])
def test_missing_library_is_rebuilt_or_raises(tmp_path, monkeypatch, source_ok):
    """A checkout holds the sources, not the .so: first use builds it
    (``make -C native``); a build that fails raises — the NumPy path is
    never taken in silence."""
    native_dir = tmp_path / "native"
    native_dir.mkdir()
    for name in ("Makefile", "graph_builder.cpp"):
        text = open(os.path.join(REPO, "native", name)).read()
        if name.endswith(".cpp") and not source_ok:
            text = "this is not C++\n"
        (native_dir / name).write_text(text)
    monkeypatch.setattr(native, "_NATIVE_DIR", str(native_dir))
    monkeypatch.setattr(native, "_LIB", None)
    monkeypatch.setattr(native, "_LIB_TRIED", False)
    monkeypatch.delenv("GRAPHMINE_NATIVE_LIB", raising=False)
    if source_ok:
        lib = native._lib()
        assert lib is not None
        assert lib._name == str(native_dir / "libgraphbuild.so")
        assert hasattr(lib, "gb_build_message_csr")
    else:
        with pytest.raises(RuntimeError, match="building .* failed"):
            native._lib()
        assert native._LIB is None and not native._LIB_TRIED


def _write(tmp_path, text):
    p = tmp_path / "edges.txt"
    p.write_text(text)
    return str(p)


def test_native_matches_numpy(tmp_path):
    path = _write(tmp_path, "# header\na b\nb c\na b\n  c a\n")
    et_native = native.load_edge_list_native(path)
    et_numpy = load_edge_list(path, use_native=False)
    assert et_native.src.tolist() == et_numpy.src.tolist()
    assert et_native.dst.tolist() == et_numpy.dst.tolist()
    assert et_native.names.tolist() == et_numpy.names.tolist()


def test_native_integer_ids(tmp_path):
    path = _write(tmp_path, "10 20\n20 30\n10 30\n")
    et = native.load_edge_list_native(path)
    assert et.num_edges == 3
    assert et.names.tolist() == ["10", "20", "30"]
    assert et.src.tolist() == [0, 1, 0]


def test_native_empty_and_blank_lines(tmp_path):
    path = _write(tmp_path, "\n\n# only comments\n\n")
    et = native.load_edge_list_native(path)
    assert et.num_edges == 0 and et.num_vertices == 0


def test_native_missing_file():
    assert native.load_edge_list_native("/nonexistent/e.txt") is None


def test_native_large_roundtrip(tmp_path, rng):
    src = rng.integers(0, 1000, 20000)
    dst = rng.integers(0, 1000, 20000)
    path = _write(tmp_path, "".join(f"v{s} v{d}\n" for s, d in zip(src, dst)))
    et = native.load_edge_list_native(path)
    assert et.num_edges == 20000
    # decode back through names and compare to the original ids
    back_src = np.array([et.names[i] for i in et.src])
    assert (back_src == np.array([f"v{s}" for s in src])).all()


def test_native_message_csr_matches_numpy():
    from graphmine_tpu.graph.container import _message_csr
    from graphmine_tpu.io import native

    if not native.available():
        pytest.skip("native lib unavailable")
    rng = np.random.default_rng(3)
    src = rng.integers(0, 50, 400).astype(np.int32)
    dst = rng.integers(0, 50, 400).astype(np.int32)
    for sym in (True, False):
        pn, rn, sn, _ = _message_csr(src, dst, 50, sym, use_native=True)
        pp, rp, sp, _ = _message_csr(src, dst, 50, sym, use_native=False)
        np.testing.assert_array_equal(pn, pp)
        np.testing.assert_array_equal(rn, rp)
        np.testing.assert_array_equal(sn, sp)
    with pytest.raises(ValueError):
        native.build_message_csr(np.array([99], np.int32), np.array([0], np.int32), 50)


def test_native_weighted_message_csr_matches_numpy():
    """r2: the weighted build rides the native counting sort too (was
    NumPy-argsort-only); layout AND weight permutation must match the
    NumPy path bit-for-bit."""
    from graphmine_tpu.graph.container import _message_csr
    from graphmine_tpu.io import native

    if not native.available():
        pytest.skip("native lib unavailable")
    if not hasattr(native._lib(), "gb_build_message_csr_weighted"):
        # stale .so: the wrapper would fall back to NumPy and this test
        # would vacuously compare NumPy against NumPy
        pytest.skip("libgraphbuild.so predates the weighted builder")
    rng = np.random.default_rng(5)
    src = rng.integers(0, 50, 400).astype(np.int32)
    dst = rng.integers(0, 50, 400).astype(np.int32)
    w = rng.uniform(0.1, 9.0, 400).astype(np.float32)
    for sym in (True, False):
        pn, rn, sn, wn = _message_csr(src, dst, 50, sym, use_native=True, weights=w)
        pp, rp, sp, wp = _message_csr(src, dst, 50, sym, use_native=False, weights=w)
        assert wn is not None
        np.testing.assert_array_equal(pn, pp)
        np.testing.assert_array_equal(rn, rp)
        np.testing.assert_array_equal(sn, sp)
        np.testing.assert_array_equal(wn, wp)
    with pytest.raises(ValueError):
        native.build_message_csr(
            np.array([99], np.int32), np.array([0], np.int32), 50,
            weights=np.array([1.0], np.float32),
        )
