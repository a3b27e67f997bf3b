"""Quickstart: the paper's FP8-via-integer arithmetic in 60 seconds.

Run:  PYTHONPATH=src python examples/quickstart.py
"""
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1] / "src"))

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import E4M3, E5M2, Oracle, encode, decode, lns_op, quantize
from repro.caches import enable_compile_cache
from repro.configs import get_config
from repro.data.pipeline import DataConfig, Dataset
from repro.models import Model
from repro.optim import adamw
from repro.runtime import steps
from repro.kernels import ops as kops


def main():
    enable_compile_cache()
    print("=" * 70)
    print("1. Scalar FP8 multiplication WITHOUT a multiplier (E4M3, round-to-even)")
    print("=" * 70)
    for a, b in [(1.5, 2.0), (3.25, 0.375), (-7.0, 0.109375), (13.0, 13.0)]:
        xa = encode(jnp.float32(a), E4M3)
        xb = encode(jnp.float32(b), E4M3)
        # The paper's circuit: one 8-bit integer add + a carry-in boolean.
        prod = lns_op(E4M3, "mul", "rne", xa, xb)
        got = float(E4M3.decode(np.asarray(prod)))
        exact = a * b
        print(f"  {a:8} * {b:10} = {exact:10.5f} -> FP8 {got:10.5f} "
              f"(codes {int(xa):#04x}+{int(xb):#04x} -> {int(prod):#04x})")

    print()
    print("=" * 70)
    print("2. All six ops, correctly rounded, verified against the exact oracle")
    print("=" * 70)
    oracle = Oracle(E5M2)
    X = np.arange(256, dtype=np.uint8)
    for op in ("square", "recip", "sqrt", "rsqrt"):
        expected, valid = oracle.quantize_all(op, X)
        got = np.asarray(lns_op(E5M2, op, "rne", jnp.asarray(X)))
        ok = (got[valid] == expected["rne"][valid]).all()
        print(f"  e5m2 {op:6s} RN_e: {int(valid.sum()):4d}/256 in-domain inputs, "
              f"all correctly rounded: {bool(ok)}")

    print()
    print("=" * 70)
    print("3. A quantized matmul through the Pallas LNS kernel (integer products)")
    print("=" * 70)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((64, 128)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((128, 32)).astype(np.float32) * 0.1)
    qx = quantize(x, "e4m3")
    qw = quantize(w, "e4m3")
    out_lns = kops.matmul_q(qx, qw, impl="lns", interpret=True)
    out_f32 = x @ w
    rel = np.abs(np.asarray(out_lns) - np.asarray(out_f32)) / (np.abs(np.asarray(out_f32)) + 1e-3)
    print(f"  [64,128]@[128,32]: median relative error vs f32 = {np.median(rel):.4f}")
    print(f"  (every product was an 8-bit integer ADD, never a multiply)")

    print()
    print("=" * 70)
    print("4. Train a tiny LM with the FP8-LNS fabric (loss should drop)")
    print("=" * 70)

    cfg = get_config("qwen2-0.5b", smoke=True, quant="fp8_lns")
    model = Model(cfg, max_seq=32)
    data = Dataset(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8, kind="arith"))
    step = jax.jit(steps.build_train_step(model, adamw.OptConfig(lr=1e-3, warmup_steps=5, total_steps=40)))
    state = steps.make_train_state(model, jax.random.PRNGKey(0))
    losses = []
    for i in range(40):
        state, m = step(state, jax.tree.map(jnp.asarray, data.batch(i)))
        if i % 10 == 0 or i == 39:
            losses.append(float(m["loss"]))
            print(f"  step {i:3d}  loss {losses[-1]:.4f}")
    assert losses[-1] < losses[0], "loss should decrease"
    print("  quantized training works.")


if __name__ == "__main__":
    main()
