"""Native C++ host augmentation kernel vs the numpy reference.

The native path only moves memory — Python draws the randomness — so on
the same (ys, xs, flip) draws the two implementations must be
bit-identical, including the zero-fill border cases at the offset extremes.
"""
import numpy as np
import pytest

from ddp_tpu.data import native
from ddp_tpu.data.augment import _numpy_crop_flip, random_crop_flip


def _require_native():
    if native.get_lib() is None:
        pytest.skip("native toolchain unavailable")


def test_native_matches_numpy_random():
    _require_native()
    rng = np.random.default_rng(0)
    batch = rng.integers(0, 256, (64, 32, 32, 3), dtype=np.uint8)
    ys = rng.integers(0, 9, 64)
    xs = rng.integers(0, 9, 64)
    flip = rng.random(64) < 0.5
    out_native = native.crop_flip(batch, ys, xs, flip)
    np.testing.assert_array_equal(out_native,
                                  _numpy_crop_flip(batch, ys, xs, flip))


def test_native_matches_numpy_extremes():
    """All 4 offset corners x flip: maximal zero-fill regions."""
    _require_native()
    rng = np.random.default_rng(1)
    corners = [(y, x, f) for y in (0, 8) for x in (0, 8) for f in (0, 1)]
    batch = rng.integers(0, 256, (len(corners), 32, 32, 3), dtype=np.uint8)
    ys = np.array([c[0] for c in corners])
    xs = np.array([c[1] for c in corners])
    flip = np.array([bool(c[2]) for c in corners])
    out_native = native.crop_flip(batch, ys, xs, flip)
    np.testing.assert_array_equal(out_native,
                                  _numpy_crop_flip(batch, ys, xs, flip))


def test_dispatch_is_deterministic_across_backends(monkeypatch):
    """random_crop_flip gives the same result whether or not the native
    kernel is in use (same generator state -> same draws -> same bytes)."""
    _require_native()
    rng = np.random.default_rng(7)
    batch = rng.integers(0, 256, (32, 32, 32, 3), dtype=np.uint8)
    out_native = random_crop_flip(batch, np.random.default_rng(42))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", True)
    out_numpy = random_crop_flip(batch, np.random.default_rng(42))
    np.testing.assert_array_equal(out_native, out_numpy)


def test_failed_build_says_so_once(tmp_path, monkeypatch, capsys):
    """A build that fails is not silent: one stderr line carrying the
    compiler's own message, then the numpy path (None) on every call."""
    bad = tmp_path / "broken.cpp"
    bad.write_text("this is not C++\n")
    monkeypatch.setattr(native, "_SRC", str(bad))
    monkeypatch.setattr(native, "NATIVE_BUILD_DIR", str(tmp_path / "build"))
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    assert native.get_lib() is None
    err = capsys.readouterr().err
    assert err.count("native augment kernel unavailable") == 1
    assert "error" in err and "broken.cpp" in err  # g++'s message
    assert native.get_lib() is None
    assert capsys.readouterr().err == ""
