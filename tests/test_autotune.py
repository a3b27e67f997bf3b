"""Autotuner behavior: defaults, cache persistence, block normalization."""
import json

import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels.lns_matmul import DEFAULT_CK, normalize_blocks


@pytest.fixture()
def tuner_cache(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    autotune.clear_memory_cache()
    yield path
    autotune.clear_memory_cache()


def test_interpret_defaults_without_measurement(tuner_cache, monkeypatch):
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)
    blocks = autotune.matmul_blocks(512, 512, 512, fmt="e4m3", impl="lns",
                                    interpret=True)
    assert blocks == (128, 128, 128, 64)
    assert autotune.matmul_blocks(512, 512, 512, fmt="e4m3",
                                  impl="fused_dequant", interpret=True) == (128, 128, 128)
    assert autotune.elementwise_block_rows(10_000, fmt="e4m3", op="mul",
                                           interpret=True) == 256
    assert autotune.flash_blocks(256, 256, 64, 64, interpret=True) == (128, 128)
    # defaults are heuristics, not measurements: nothing is persisted
    assert not tuner_cache.exists()


def test_defaults_clamp_to_problem(tuner_cache):
    assert autotune.matmul_blocks(8, 16, 4, fmt="e4m3", impl="lns",
                                  interpret=True) == (8, 16, 4, 4)


def test_cache_roundtrip_and_persistence(tuner_cache):
    autotune._store("matmul|cpu|i1|64x64x64|e4m3|lns|rne", (32, 32, 32, 8))
    # a fresh in-process view must re-read the file
    autotune.clear_memory_cache()
    assert tuner_cache.exists()
    blocks = autotune.matmul_blocks(64, 64, 64, fmt="e4m3", impl="lns",
                                    interpret=True)
    assert blocks == (32, 32, 32, 8)
    data = json.loads(tuner_cache.read_text())
    assert data["matmul|cpu|i1|64x64x64|e4m3|lns|rne"] == [32, 32, 32, 8]


def test_forced_measurement_populates_cache(tuner_cache, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    # tiny problem so the measured sweep is quick even in interpret mode;
    # candidate grid is empty at this size -> falls back to the default,
    # which is still measured-and-cached
    blocks = autotune.matmul_blocks(16, 16, 16, fmt="e4m3", impl="lns",
                                    interpret=True)
    assert len(blocks) == 4
    autotune.clear_memory_cache()
    again = autotune.matmul_blocks(16, 16, 16, fmt="e4m3", impl="lns",
                                   interpret=True)
    assert tuple(again) == tuple(blocks)
    assert tuner_cache.exists()


def test_new_cache_keys_embed_device_kind(tuner_cache, monkeypatch):
    """A tiling measured on one device model must not be replayed on a
    different one sharing the cache file: fresh entries carry
    jax.devices()[0].device_kind in the key."""
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    autotune.matmul_blocks(16, 16, 16, fmt="e4m3", impl="lns", interpret=True)
    data = json.loads(tuner_cache.read_text())
    kind = autotune._device_kind()
    assert kind not in ("", "unknown")
    (key,) = data.keys()
    assert key.startswith(f"matmul|cpu|{kind}|i1|16x16x16|")


def test_pre_device_kind_cache_entries_stay_readable(tuner_cache):
    """Entries written before the device-kind key field existed resolve
    via the legacy-format fallback, and a device-kind entry wins over a
    legacy one for the same problem."""
    legacy = "matmul|cpu|i1|48x48x48|e4m3|lns|rne"
    autotune._store(legacy, (16, 16, 16, 8))
    autotune.clear_memory_cache()
    assert autotune.matmul_blocks(48, 48, 48, fmt="e4m3", impl="lns",
                                  interpret=True) == (16, 16, 16, 8)
    new = f"matmul|cpu|{autotune._device_kind()}|i1|48x48x48|e4m3|lns|rne"
    autotune._store(new, (32, 32, 32, 8))
    assert autotune.matmul_blocks(48, 48, 48, fmt="e4m3", impl="lns",
                                  interpret=True) == (32, 32, 32, 8)


def test_choose_impl_on_cpu_is_xla(tuner_cache, monkeypatch):
    monkeypatch.delenv("REPRO_MATMUL_IMPL", raising=False)
    assert autotune.choose_matmul_impl(64, 64, 64, fmt="e4m3") == "xla"
    monkeypatch.setenv("REPRO_MATMUL_IMPL", "lns")
    assert autotune.choose_matmul_impl(64, 64, 64, fmt="e4m3") == "lns"


def test_normalize_blocks_ck_divides_bk():
    # ck clamps to the largest divisor of the clamped bk
    assert normalize_blocks((128, 128, 128, 48), 512, 512, 512) == (128, 128, 128, 32)
    assert normalize_blocks((128, 128, 128), 512, 512, 512) == (128, 128, 128, DEFAULT_CK)
    assert normalize_blocks((128, 128, 128, 16), 100, 70, 50) == (100, 70, 50, 10)
    assert normalize_blocks((32, 32, 32, 64), 8, 8, 3) == (8, 8, 3, 3)


def test_autotuned_matmul_matches_pinned_blocks():
    from repro.kernels.lns_matmul import lns_matmul

    import jax.numpy as jnp

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(8, 120, size=(64, 32)).astype(np.uint8))
    w = jnp.asarray(rng.integers(8, 120, size=(32, 48)).astype(np.uint8))
    auto = lns_matmul(x, w, fmt="e4m3", interpret=True)
    pinned = lns_matmul(x, w, fmt="e4m3", interpret=True, blocks=(64, 48, 32, 8))
    np.testing.assert_allclose(np.asarray(auto), np.asarray(pinned), rtol=1e-6)


def test_paged_blocks_is_the_fixed_walk_block(tuner_cache, monkeypatch):
    """The paged kernel's block shape is a constant: not measured even when
    measuring is forced, and not read from or written to the cache."""
    shape = dict(B=2, maxp=3, page=4, KV=1, G=2, hd=8)
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    assert (autotune.paged_blocks(**shape, fmt="e4m3", interpret=True)
            == autotune.PAGED_BLOCKS)
    assert not tuner_cache.exists() or "paged" not in tuner_cache.read_text()
