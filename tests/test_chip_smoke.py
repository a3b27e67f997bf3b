"""The chip bring-up plumbing, on the CPU.

``chip_smoke.py`` runs on a TPU only; its phases are functions of a model
config, so here they run at ``cfg.smoke()`` size (the paged kernel in
interpret mode).  Also pinned: the script refuses a CPU backend and a
directory without the repository, the compile-cache placement, the
autotuner's refusal handling, and the paged kernel's shard_map launch on
a KV-head-sharded mesh.
"""
import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro import caches
from repro.configs import get_config
from repro.kernels import autotune

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod._import_repro()
    return mod


@pytest.fixture(scope="module")
def cs():
    return _load_chip_smoke()


@pytest.fixture(scope="module")
def cfg():
    return get_config("qwen2-0.5b", smoke=True, policy="serve_fp8_paged")


def test_kernel_phase_matches_ref(cs, cfg):
    r = cs.phase_kernel(cfg, seed=0, slots=3, ctx=40, page_size=8,
                        num_pages=64)
    # interpret mode pins the XLA-CPU bit-identity barriers: exact
    assert r == {"unfused_rel_err": 0.0, "fused_rel_err": 0.0,
                 "fused_cache_equal": True}


def test_engine_phases(cs, cfg):
    queue = cs.make_queue(cfg, seed=0, requests=3, prompt_lens=(20, 40))
    assert [len(p) for p in queue] == [len(p) for p in cs.make_queue(
        cfg, seed=0, requests=3, prompt_lens=(20, 40))]
    eng = cs.build_engine(cfg, seed=0, slots=3, max_seq=48, page_size=8,
                          num_pages=64)
    steps = cs.phase_compiled_steps(eng, chunk=4)
    # the CPU serving path is the vectorized pure-JAX attention: no kernel
    assert set(steps) == {"mixed", "decode"}
    assert all(sec > 0 and n == 0 and need > 0
               for sec, n, need in steps.values())
    outputs, stats = cs.phase_serve(eng, queue, gen=6, chunk=4, seed=0)
    assert stats["terminal"] == {"finished": 3}
    assert all(len(o) == 6 for o in outputs.values())
    r = cs.phase_logits(eng, queue[0], chunk=4)
    assert r["rel_l2"] <= cs.LOGITS_REL_L2, r


def test_deployment_pages_fills_the_memory_share(cs, cfg):
    kw = dict(slots=3, page_size=8, max_seq=48, chunk=4)
    need = cs.step_memory(cfg, pages=256, **kw)
    assert need > cs.step_memory(cfg, pages=64, **kw)
    pages = cs.deployment_pages(cfg, limit=need / cs.MEMORY_SHARE + 1,
                                probe=(64, 128), **kw)
    assert pages == 256


def test_reference_forward_matches_bf16_free_engine(cs, cfg):
    """With every numerics knob off (f32 params, no FP8 anywhere) the
    engine and the independent f32 forward agree to f32 noise."""
    import dataclasses

    plain = dataclasses.replace(
        get_config("qwen2-0.5b", smoke=True, policy="train_bf16"),
        param_dtype="float32")
    eng = cs.build_engine(plain, seed=1, slots=1, max_seq=24, page_size=8,
                          num_pages=8)
    prompt = np.arange(3, 21) % plain.vocab
    assert cs.phase_logits(eng, prompt, chunk=4)["rel_l2"] < 1e-4


def test_main_refuses_cpu_backend(cs):
    with pytest.raises(SystemExit) as e:
        cs.main([])
    assert "no TPU" in str(e.value)


def test_script_alone_fails_without_result(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    out = subprocess.run(
        [sys.executable, "chip_smoke.py"], cwd=tmp_path, capture_output=True,
        text=True, timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    assert "src/repro is not" in out.stderr


def test_compile_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert caches.compile_cache_dir() == tmp_path
    before = jax.config.jax_compilation_cache_dir
    assert caches.enable_compile_cache() == tmp_path
    # JAX reads the variable itself; no directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_dir_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = caches.compile_cache_dir()
    assert first == ROOT / ".cache" / "jax" == caches.compile_cache_dir()
    before = jax.config.jax_compilation_cache_dir
    try:
        assert caches.enable_compile_cache() == first
        assert jax.config.jax_compilation_cache_dir == str(first)
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
    monkeypatch.delenv("REPRO_AUTOTUNE_CACHE", raising=False)
    assert autotune.cache_path() == ROOT / ".cache" / "autotune.json"


@pytest.fixture()
def tuner_cache(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    monkeypatch.setenv("REPRO_AUTOTUNE", "force")
    autotune.clear_memory_cache()
    yield path
    autotune.clear_memory_cache()


def test_autotune_raises_when_every_candidate_is_refused(tuner_cache,
                                                          monkeypatch):
    from repro.kernels import lns_matmul

    def refuse(*a, **k):
        raise NotImplementedError("Unimplemented primitive in Pallas TPU "
                                  "lowering: dynamic_slice")

    monkeypatch.setattr(lns_matmul, "lns_matmul", refuse)
    with pytest.warns(UserWarning, match="refused by the compiler"):
        with pytest.raises(RuntimeError, match="refused every matmul"):
            autotune.matmul_blocks(16, 16, 16, fmt="e4m3", impl="lns",
                                   interpret=True)
    assert not tuner_cache.exists()  # nothing unmeasured was cached


def test_autotune_skips_a_refused_candidate_with_its_reason(tuner_cache):
    from repro.serving.telemetry import default_registry

    def make_fn(cand):
        if cand == "bad":
            def fail():
                raise ValueError("block shape (1, 1) not divisible")
            return fail
        return lambda: jax.numpy.zeros(4)

    with pytest.warns(UserWarning, match="block shape"):
        best = autotune._measure_best("k|test", ["bad", "good"], make_fn,
                                      kernel="probe", site="unit")
    assert best == "good"
    assert "autotune_refused_total" in default_registry().to_prometheus()


def _run_on_two_host_devices(body: str) -> str:
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=2")
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                         capture_output=True, text=True, timeout=600, env=env,
                         cwd=ROOT)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-3000:]
    return out.stdout


def test_mesh_phase_tp2_equals_tp1():
    _run_on_two_host_devices("""
    import chip_smoke as cs
    cs._import_repro()
    from repro.configs import get_config
    cfg = get_config("qwen2-0.5b", smoke=True, policy="serve_fp8_paged")
    q = cs.make_queue(cfg, seed=0, requests=4, prompt_lens=(20, 40))
    r = cs.phase_mesh(cfg, q, seed=0, slots=3, max_seq=48, page_size=8,
                      gen=6, chunk=4)
    assert r["tokens_equal"] and r["cache_equal"], r
    assert r["finished"] == {"tp1": 4, "tp2": 4}, r
    print("OK")
    """)


def test_paged_kernel_under_kv_sharded_mesh_matches_single_device():
    """The kernel launch runs under shard_map on a KV-head-sharded mesh and
    gives the single-device bits (interpret mode, 2 host devices)."""
    body = """
    import jax, numpy as np, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core.quant import encode
    from repro.configs import get_config
    from repro.kernels.paged_attention import (fused_decode_write_attend,
                                               paged_decode_attention)
    from repro.launch.mesh import make_production_mesh
    from repro.parallel.hints import serve_hint_specs, use_hints

    mesh = make_production_mesh(shape=(1, 2))
    cfg = get_config("qwen2-0.5b", smoke=True, policy="serve_fp8_paged")
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    KV, H, hd, P_, page = 2, 4, 16, 16, 8
    kp, vp = encode(f(P_, page, KV, hd), "e5m2"), encode(f(P_, page, KV, hd), "e5m2")
    ks = vs = jnp.ones((P_,), jnp.float32)
    bt = jnp.asarray(np.arange(1, 7, dtype=np.int32).reshape(2, 3))
    ln = jnp.asarray([11, 20], jnp.int32)
    q, kn, vn = f(2, 1, H, hd), f(2, KV, hd), f(2, KV, hd)

    def run(fused):
        if fused:
            return fused_decode_write_attend(
                q, kn, vn, kp, vp, ks, vs, bt, ln, fmt="e5m2", n_kv_heads=KV,
                kv_mode="rne", impl="kernel", interpret=True)
        return (paged_decode_attention(q, kp, vp, ks, vs, bt, ln,
                                       fmt="e5m2", n_kv_heads=KV,
                                       impl="kernel", interpret=True),)

    for fused in (False, True):
        want = run(fused)
        with use_hints(mesh, serve_hint_specs(cfg, mesh)):
            sh = NamedSharding(mesh, P(None, None, "model", None))
            kp, vp = jax.device_put(kp, sh), jax.device_put(vp, sh)
            got = run(fused)
        for a, b in zip(got, want):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    print("OK")
    """
    _run_on_two_host_devices(body)
