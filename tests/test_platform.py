"""utils/platform.py: the compile-cache rule, the device line and the
virtual-CPU-mesh child environment the entry points share."""
import os

import jax

from ddp_tpu.parallel import make_mesh
from ddp_tpu.utils import platform

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_compile_cache_env_set_is_left_alone(tmp_path, monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set: JAX bound it at import — the helper
    neither touches jax.config nor creates a directory."""
    outer = tmp_path / "outer"
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(outer))
    before = jax.config.jax_compilation_cache_dir
    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a, **k: updates.append(a))
    platform.enable_compile_cache()
    assert updates == []
    assert jax.config.jax_compilation_cache_dir == before
    assert not outer.exists()


def test_compile_cache_default_is_in_the_checkout(monkeypatch):
    """Unset: <checkout>/.jax_cache — a fixed path, not the home
    directory, a temp name, a pid or a time."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        platform.enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            _REPO, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_device_line_is_what_chip_smoke_parses():
    import chip_smoke
    line = platform.device_line(make_mesh(8), native_augment="on")
    m = chip_smoke.DEVICE_RE.search("noise\n" + line + "\nmore\n")
    assert m and m.groups() == (
        "cpu", "cpu", "8", "data=8", "0,1,2,3,4,5,6,7",
        " native_augment=on")
    two_d = platform.device_line(make_mesh(shape=(2, 2)))
    assert " mesh=data=2,model=2 ids=0,1,2,3" in two_d


def test_cpu_device_env_sets_platform_and_one_count_flag():
    env = platform.cpu_device_env(
        4, {"XLA_FLAGS": "--foo --xla_force_host_platform_device_count=8",
            "JAX_PLATFORMS": "tpu"})
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["XLA_FLAGS"].split() == [
        "--foo", "--xla_force_host_platform_device_count=4"]
