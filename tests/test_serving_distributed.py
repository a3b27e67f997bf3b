"""Tensor-parallel paged serving on 8 forced host devices
(subprocess-isolated).

Each test runs a script in a fresh interpreter with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the flag must be
set before jax initializes, and the main test process must keep its
single device for the other suites).

The acceptance contract (ISSUE 10): a TP=2 engine — continuous
scheduler, stochastic KV rounding ON, prefix cache on and off — streams
token-BIT-IDENTICAL outputs to the single-device engine, the paged KV
cache matches bitwise at the end of the run, and every serving feature
survives the mesh: preemption spill/restore, chaos kill + snapshot
restore, elastic TP=1 <-> TP=2 snapshot reshard, sharded-QTensor static
weights.  The page-sharded LSE-psum combine (the path that is allclose
but NOT bit-exact) is pinned separately against the full-batch kernel.
"""
import os
import pathlib
import subprocess
import sys
import textwrap

import pytest

SRC = str(pathlib.Path(__file__).resolve().parents[1] / "src")


def run_script(body: str, timeout=900) -> str:
    env = dict(
        os.environ,
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=SRC,
        JAX_PLATFORMS="cpu",
    )
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(body)],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


COMMON = """
import jax, numpy as np, jax.numpy as jnp
from repro.configs import get_config
from repro.launch import serve
from repro.launch.mesh import make_production_mesh
from repro.serving import (ContinuousScheduler, FaultPlan, Request,
                           load_snapshot, save_snapshot)

assert len(jax.devices()) >= 2, jax.devices()
cfg = get_config("qwen2-0.5b", smoke=True, policy="serve_fp8_paged")
mesh2 = make_production_mesh(shape=(1, 2))

def engine(mesh=None, **kw):
    kw.setdefault("slots", 2)
    kw.setdefault("max_seq", 64)
    kw.setdefault("page_size", 8)
    kw.setdefault("rng_seed", 0)
    # the acceptance gate is bit-identity WITH the stochastic serving
    # numerics, not despite them
    kw.setdefault("stochastic_kv", True)
    return serve.Engine(cfg, cache_impl="paged", mesh=mesh, **kw)

def prompts(n=4, shared=16, tail=8, seed=0):
    rng = np.random.default_rng(seed)
    s = rng.integers(0, cfg.vocab, size=shared)
    return [np.concatenate([s, rng.integers(0, cfg.vocab, size=tail)])
            for _ in range(n)]

def cache_leaves(eng):
    return jax.tree.leaves(jax.device_get(eng.cache))
"""


def test_tp2_tokens_and_cache_bit_identical_prefix_on_and_off():
    """The tentpole gate: single-device vs TP=2 under the continuous
    scheduler, stochastic KV ON — token streams AND the final paged KV
    cache (codes + scales) are bitwise equal, prefix cache on and off."""
    out = run_script(COMMON + """
for prefix in (False, True):
    runs = []
    for mesh in (None, mesh2):
        eng = engine(mesh, prefix_cache=prefix)
        outs, stats = serve.run(eng, prompts(), gen=12, quiet=True,
                                scheduler="continuous")
        runs.append((eng, outs, stats))
    (e1, o1, s1), (e2, o2, s2) = runs
    assert e2.tp_size == 2
    assert set(o1) == set(o2) and all(o1[r] == o2[r] for r in o1), \\
        (prefix, o1, o2)
    for a, b in zip(cache_leaves(e1), cache_leaves(e2)):
        np.testing.assert_array_equal(a, b)
    if prefix:
        assert e2.pool.prefix_hits > 0  # the shared prefix really was reused
    print(f"prefix={prefix} bitwise OK")
print("OK")
""")
    assert "OK" in out


def test_tp2_static_qtensor_weights_bit_identical():
    """static_weights=True: quantized QTensor carriers with device-sharded
    codes and replicated scales serve the same token streams as the
    single-device static engine."""
    out = run_script(COMMON + """
runs = []
for mesh in (None, mesh2):
    eng = engine(mesh, static_weights=True)
    outs, _ = serve.run(eng, prompts(), gen=10, quiet=True,
                        scheduler="continuous")
    runs.append(outs)
o1, o2 = runs
assert set(o1) == set(o2) and all(o1[r] == o2[r] for r in o1), (o1, o2)
print("OK")
""")
    assert "OK" in out


def test_preempt_restore_mid_decode_on_mesh():
    """A tight pool forces a preemption spill on the TP=2 engine; a
    snapshot taken while a request sits PREEMPTED restores into a fresh
    mesh engine that finishes with the single-device run's tokens."""
    out = run_script(COMMON + """
import tempfile
queue = prompts(n=4, shared=0, tail=6, seed=8)
geo = dict(slots=3, max_seq=16, page_size=4, num_pages=7)

# fault-free single-device reference
ref = engine(None, **geo)
base, _ = serve.run(ref, [q.copy() for q in queue], gen=6, quiet=True,
                    scheduler="continuous")

def build():
    eng = engine(mesh2, **geo)  # tight: forces spills
    return eng, ContinuousScheduler(eng, chunk=4)

eng, sched = build()
for i, p in enumerate(queue):
    sched.add(Request(rid=i, prompt=p.copy(), gen=6))
for _ in range(200):
    sched.step()
    if sched.preempted:
        break
else:
    raise AssertionError("pool never forced a preemption")
d = tempfile.mkdtemp()
save_snapshot(d, eng, sched)
eng2, sched2 = build()
step = load_snapshot(d, eng2, sched2)
assert step == sched.steps
assert len(sched2.preempted) == len(sched.preempted)
out1 = sched.run()
out2 = sched2.run()
assert out2 == out1 == base, (out1, out2, base)
eng2.pool.assert_invariants()
print("OK")
""")
    assert "OK" in out


def test_chaos_kill_and_restore_on_mesh_bit_identical():
    """Engine killed at step N mid-stream, rebuilt ON THE MESH and
    restored from the latest snapshot: every request's final output is
    bit-identical to the fault-free single-device run."""
    out = run_script(COMMON + """
import tempfile
from repro.runtime import fault

queue = prompts(n=4, shared=4, tail=4, seed=9)
geo = dict(slots=2, max_seq=16, page_size=4)

base, base_stats = fault.run_serving(lambda: engine(None, **geo), queue,
                                     gen=6, log=lambda *a: None)
assert base_stats["restarts"] == 0
d = tempfile.mkdtemp()
out, stats = fault.run_serving(
    lambda: engine(mesh2, **geo), queue, gen=6, log=lambda *a: None,
    chaos=FaultPlan(kill_at_step=7), ckpt_dir=d, snapshot_every=3,
)
assert stats["restarts"] == 1 and stats["chaos"]["killed"] == 1
assert out == base, (out, base)
assert stats["terminal"]["finished"] == 4
print("OK")
""")
    assert "OK" in out


def test_elastic_snapshot_reshard_tp1_tp2_both_ways():
    """Elastic serving snapshots: a run snapshotted mid-decode on TP=1
    restores into a TP=2 engine (and vice versa) and finishes with the
    uninterrupted run's tokens — cache leaf shapes are mesh-independent,
    so the snapshot is the reshard point."""
    out = run_script(COMMON + """
import tempfile
queue = prompts()

ref = engine(None)
sref = ContinuousScheduler(ref, chunk=4)
for i, p in enumerate(queue):
    sref.add(Request(rid=i, prompt=p.copy(), gen=10))
base = sref.run()

for src_mesh, dst_mesh, tag in ((None, mesh2, "1->2"), (mesh2, None, "2->1")):
    eng = engine(src_mesh)
    sched = ContinuousScheduler(eng, chunk=4)
    for i, p in enumerate(queue):
        sched.add(Request(rid=i, prompt=p.copy(), gen=10))
    for _ in range(6):  # partway: prefills done, decode in flight
        sched.step()
    assert sched.pending(), "snapshot must land mid-stream"
    d = tempfile.mkdtemp()
    save_snapshot(d, eng, sched)
    eng2 = engine(dst_mesh)
    sched2 = ContinuousScheduler(eng2, chunk=4)
    step = load_snapshot(d, eng2, sched2)
    assert step == sched.steps
    out2 = sched2.run()
    assert out2 == base, (tag, out2, base)
    print(tag, "OK")
print("OK")
""")
    assert "OK" in out


def test_lse_psum_combine_matches_full_batch_allclose():
    """The page-sharded flash-decoding split: each shard computes its
    pages' softmax partials, combine_partials_psum merges them with one
    pmax + two psums inside shard_map.  Allclose to the full-batch
    kernel — and documented as NOT the bit-exact path (merge order moves
    with the shard count), which is why the engine shards heads."""
    out = run_script("""
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_test_mesh
from repro.kernels.paged_attention import (combine_partials_psum,
                                           paged_attention_batch,
                                           paged_attention_partials)

B, KV, G, hd, page, maxp = 2, 2, 2, 8, 4, 4
rng = np.random.default_rng(0)
P_pages = B * maxp + 1
q = jnp.asarray(rng.standard_normal((B, KV, G, hd)), jnp.float32)
kp = jnp.asarray(rng.standard_normal((P_pages, page, KV, hd)), jnp.float32)
vp = jnp.asarray(rng.standard_normal((P_pages, page, KV, hd)), jnp.float32)
ones = jnp.ones((P_pages,), jnp.float32)
tables = jnp.arange(1, B * maxp + 1, dtype=jnp.int32).reshape(B, maxp)
lengths = jnp.full((B,), maxp * page, jnp.int32)  # full pages: mask-free

full = paged_attention_batch(q, kp, vp, ones, ones, tables, lengths,
                             fmt=None, mode=None, page_size=page,
                             KV=KV, G=G)

mesh = make_test_mesh((2,), ("x",))
half = maxp // 2

def shard_fn(tbl):
    m, l, o = paged_attention_partials(
        q, kp, vp, ones, ones, tbl,
        jnp.full((B,), half * page, jnp.int32),
        fmt=None, mode=None, page_size=page, KV=KV, G=G,
    )
    return combine_partials_psum(m, l, o, "x")

split = jax.shard_map(shard_fn, mesh=mesh, in_specs=P(None, "x"),
                  out_specs=P(), check_vma=False)(tables)
np.testing.assert_allclose(np.asarray(split), np.asarray(full),
                           rtol=2e-5, atol=2e-6)
print("OK")
""")
    assert "OK" in out
