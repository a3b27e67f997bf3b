"""Training driver: mesh + sharded state + crash-safe loop.

Scales from single-CPU smoke runs to the production mesh — the same loop
the dry-run lowers.  Examples:

  # CPU e2e demo (learnable synthetic data, loss visibly drops):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 60 --batch 8 --seq 64 --data arith

  # FP8-LNS quantized training (the paper's technique end to end):
  PYTHONPATH=src python -m repro.launch.train --arch qwen2-0.5b --smoke \
      --steps 60 --batch 8 --seq 64 --data arith --quant fp8_lns
"""
from __future__ import annotations

import argparse
import json
import pathlib

import jax
import jax.numpy as jnp
import numpy as np

from ..caches import enable_compile_cache
from ..configs import get_config
from ..data.pipeline import DataConfig, Dataset
from ..models import Model
from ..optim import adamw
from ..parallel import sharding
from ..parallel.hints import default_hint_specs, use_hints
from ..runtime import fault, steps


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--quant", default="none")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--data", default="arith", choices=["arith", "synthetic", "memmap"])
    ap.add_argument("--data-path", default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--mesh", default="1x1", help="DATAxMODEL, e.g. 2x4")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = get_config(args.arch, smoke=args.smoke, quant=args.quant)
    model = Model(cfg, max_seq=args.seq)
    opt_cfg = adamw.OptConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps)

    d, m = (int(x) for x in args.mesh.split("x"))
    use_mesh = d * m > 1
    if use_mesh:
        from .mesh import make_test_mesh

        mesh = make_test_mesh((d, m), ("data", "model"))
    else:
        mesh = None

    data = Dataset(DataConfig(
        vocab=cfg.vocab, seq_len=args.seq, global_batch=args.batch,
        seed=args.seed, kind=args.data, path=args.data_path,
    ))

    def init_state():
        return steps.make_train_state(model, jax.random.PRNGKey(args.seed))

    raw_step = steps.build_train_step(model, opt_cfg)

    if use_mesh:
        state_sds = jax.eval_shape(init_state)
        pspec = {
            "params": sharding.param_pspecs(cfg, state_sds["params"], mesh),
            "opt": {
                "m": sharding.param_pspecs(cfg, state_sds["opt"]["m"], mesh),
                "v": sharding.param_pspecs(cfg, state_sds["opt"]["v"], mesh),
                "step": jax.sharding.PartitionSpec(),
            },
        }
        bspec = sharding.batch_pspecs(cfg, mesh)
        state_sh = sharding.named(mesh, pspec)
        batch_sh = sharding.named(mesh, bspec)
        with mesh, use_hints(mesh, default_hint_specs(cfg, mesh)):
            train_step = jax.jit(
                raw_step, in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None), donate_argnums=(0,),
            )
            init_jit = jax.jit(init_state, out_shardings=state_sh)
        to_device = lambda b: {
            k: jax.device_put(v, batch_sh[k]) for k, v in b.items()
        }
        state_shardings = state_sh
    else:
        train_step = jax.jit(raw_step, donate_argnums=(0,))
        init_jit = jax.jit(init_state)
        to_device = lambda b: jax.tree.map(jnp.asarray, b)
        state_shardings = None

    ctx = (
        use_hints(mesh, default_hint_specs(cfg, mesh)) if use_mesh
        else _null_ctx()
    )
    with ctx:
        state, history = fault.run_training(
            train_step=train_step,
            init_state=init_jit,
            dataset=data,
            max_steps=args.steps,
            ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every,
            state_shardings=state_shardings,
            to_device=to_device,
        )
    out = pathlib.Path(args.ckpt_dir) / "history.json"
    out.write_text(json.dumps(history, indent=1))
    print(f"[train] done: {len(history)} log points -> {out}")
    if len(history) >= 2:
        print(f"[train] loss {history[0]['loss']:.4f} -> {history[-1]['loss']:.4f}")
    return history


class _null_ctx:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


if __name__ == "__main__":
    main()
