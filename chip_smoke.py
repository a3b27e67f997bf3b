"""Bring-up check of the paged FP8 serving path on a TPU.

    python chip_smoke.py              # one chip: qwen2-0.5b at full width
    python chip_smoke.py --chips 4    # only the tensor-parallel (--mesh 1x2)
                                      # engine against the single-device one

One process drives the chip.  With no arguments the script

  1. fails unless ``jax.devices()[0].platform == "tpu"`` — JAX falls back to
     the CPU silently when TPU start-up fails, so this is checked outright;
  2. runs the paged decode kernel at the model's widths, unfused and fused
     with the KV write, against the pure-JAX ``impl="ref"`` oracle on the
     same page codes;
  3. builds the serving engine the way ``python -m repro.launch.serve --arch
     qwen2-0.5b --policy serve_fp8_paged --scheduler continuous --cache-impl
     paged --fused-decode on`` does, with weights made from ``--seed`` and a
     page pool sized to the chip's memory by the compiler's memory
     analysis of the mixed step, compiles the two step
     traces the continuous scheduler runs and checks that both contain the
     Pallas paged kernel (``tpu_custom_call``);
  4. serves ``--requests`` seeded requests of a few hundred prompt tokens;
     every one must finish;
  5. compares the engine's logits for one prompt with a plain float32
     ``jax.numpy`` forward pass of the same weights.

Only when every phase passes does it print, as its last line,
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
The phases are functions of a model config, so a CPU test runs them at
``cfg.smoke()`` size.
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
import time

import numpy as np

SRC = pathlib.Path(__file__).resolve().parent / "src"

# Kernel vs oracle on the chip: both sides compute the same integer LNS
# products and f32 values; they differ only in the order of the f32
# additions (the head_dim sum of a score, the page-row sums, p.V and the
# page combine).  Each is a sum of at most max(head_dim, page) = 64 terms,
# so a reordering moves a score by a few f32 ulps of the sum of |terms|,
# and the scores enter exp(); 1e-4 of the output's scale bounds that with
# room.  The updated cache arrays are computed outside the kernel by the
# same XLA code on both sides and must match exactly.
KERNEL_RTOL = 1e-4

# Engine vs f32 forward: the engine keeps K/V as E5M2 page codes (2
# mantissa bits, stochastic rounding: one element can be off by up to a
# quarter of its value), quantizes each query to E5M2 for the integer-domain
# QK^T, multiplies by the paper's approximate LNS product, and runs bf16
# weights and activations.  The reference does none of this.  At
# qwen2-0.5b widths with the depth cut (CPU, seed 0, ~200-token prompts)
# the relative L2 error of the last logits was 0.14 at 4 layers and 0.17 at
# 12; with bf16 alone (no FP8 anywhere) it was 0.01, with E4M3 pages (one
# more mantissa bit) 0.07 and with round-to-nearest writes 0.09, so it is
# the pages' rounding noise and grows slowly with depth.  A wrong page,
# position, head or layer replaces the attention output outright, which is
# an error of order 1; 0.3 separates the two at 24 layers.
LOGITS_REL_L2 = 0.3

# Share of the device's memory the mixed step (weights, page pool and the
# step's temporaries) may need; the rest is left to the runtime, the
# sampler's logits and the kernel autotuner's probes.
MEMORY_SHARE = 0.9


def _import_repro():
    if not (SRC / "repro").is_dir():
        raise SystemExit(f"chip_smoke: the repository's src/repro is not "
                         f"beside this script ({SRC}); run it from a "
                         "checkout")
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))


def require_tpu(count: int = 1) -> dict:
    """The device JAX reports, or SystemExit when it is not a TPU."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU: JAX's first device is "
                         f"{d.platform!r} ({d.device_kind}); this check "
                         "runs only on a TPU")
    if len(devs) < count:
        raise SystemExit(f"chip_smoke: needs {count} TPU devices, JAX "
                         f"reports {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


# --------------------------------------------------------------------------- #
# Phase: paged kernel vs the oracle
# --------------------------------------------------------------------------- #
def phase_kernel(cfg, *, seed: int, slots: int, ctx: int, page_size: int,
                 num_pages: int) -> dict:
    """Paged decode kernel (unfused and fused) vs ``impl="ref"`` at the
    config's head geometry.  Returns the worst relative output errors."""
    import jax
    import jax.numpy as jnp

    from repro import numerics
    from repro.core.quant import encode
    from repro.kernels.paged_attention import (fused_decode_write_attend,
                                               paged_decode_attention)

    fmt = numerics.kv_format(cfg.policy)
    KV, H, hd = cfg.n_kv_heads, cfg.n_heads, cfg.hd
    maxp = -(-ctx // page_size)
    rng = np.random.default_rng(seed)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    kp = encode(f32(num_pages, page_size, KV, hd), fmt)
    vp = encode(f32(num_pages, page_size, KV, hd), fmt)
    ks = jnp.asarray(2.0 ** rng.integers(-3, 3, num_pages).astype(np.float32))
    vs = jnp.asarray(2.0 ** rng.integers(-3, 3, num_pages).astype(np.float32))
    bt = jnp.asarray(rng.permutation(np.arange(1, num_pages))[:slots * maxp]
                     .reshape(slots, maxp).astype(np.int32))
    lengths = jnp.asarray(rng.integers(1, ctx, slots).astype(np.int32))
    q = f32(slots, 1, H, hd)
    k_new, v_new = f32(slots, KV, hd), f32(slots, KV, hd)
    keys = jax.random.split(jax.random.PRNGKey(seed), slots)
    mask = jnp.asarray(rng.random(slots) < 0.9)

    def rel(a, b):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        return float(np.max(np.abs(a - b)) / max(np.max(np.abs(b)), 1e-30))

    out = {}
    with jax.default_matmul_precision("highest"):
        ker, ref = (paged_decode_attention(
            q, kp, vp, ks, vs, bt, lengths, fmt=fmt, n_kv_heads=KV,
            impl=impl) for impl in ("kernel", "ref"))
        out["unfused_rel_err"] = rel(ker, ref)
        fk, fr = (fused_decode_write_attend(
            q, k_new, v_new, kp, vp, ks, vs, bt, lengths, fmt=fmt,
            n_kv_heads=KV, kv_mode="stochastic", k_key=keys, v_key=keys,
            write_mask=mask, impl=impl) for impl in ("kernel", "ref"))
    act = np.asarray(mask)
    out["fused_rel_err"] = rel(np.asarray(fk[0])[act], np.asarray(fr[0])[act])
    out["fused_cache_equal"] = all(
        np.array_equal(np.asarray(a)[1:], np.asarray(b)[1:])
        for a, b in zip(fk[1:], fr[1:]))
    return out


# --------------------------------------------------------------------------- #
# Phase: the serving engine
# --------------------------------------------------------------------------- #
def make_queue(cfg, *, seed: int, requests: int, prompt_lens):
    """Seeded prompts, drawn the way ``repro.launch.serve`` draws them."""
    rng = np.random.default_rng(seed)
    lo, hi = prompt_lens
    return [rng.integers(0, cfg.vocab, size=int(rng.integers(lo, hi + 1)))
            for _ in range(requests)]


def step_memory(cfg, *, slots: int, page_size: int, max_seq: int, chunk: int,
                pages: int) -> int:
    """Device bytes the compiler says the engine's mixed step needs at a
    pool of ``pages``: arguments (weights, page pool) + outputs not
    aliased to them + temporaries.  The mixed step is the larger of the
    two traces: it carries the pool through its token scan."""
    import jax
    import jax.numpy as jnp

    from repro.models import Model

    model = Model(cfg, max_seq=max_seq)
    step = jax.jit(model.step_paged, static_argnames=("page_size", "fused"),
                   donate_argnums=(1,))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)
    m = step.lower(
        jax.eval_shape(model.init, jax.random.PRNGKey(0)),
        jax.eval_shape(lambda: model.make_paged_cache(
            slots, pages, page_size, max_seq)),
        i32(slots, chunk), i32(slots), i32(slots),
        i32(slots, -(-max_seq // page_size)), page_size=page_size,
        key=jax.random.PRNGKey(0), fused=True,
    ).compile().memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes)


def deployment_pages(cfg, *, slots: int, page_size: int, max_seq: int,
                     chunk: int, limit: int, probe=(4096, 8192)) -> int:
    """Page-pool size a deployment would give a device of ``limit`` bytes,
    found the way a server profiles its step before it allocates its pool:
    the largest pool whose mixed step needs at most ``MEMORY_SHARE`` of the
    device.  The need is linear in the pool; it is read from the compiler
    at two probe sizes, so the layout padding the device really allocates
    and the step's temporaries are counted."""
    lo, hi = probe
    need = [step_memory(cfg, slots=slots, page_size=page_size,
                        max_seq=max_seq, chunk=chunk, pages=p) for p in probe]
    per_page = (need[1] - need[0]) / (hi - lo)
    return int(lo + (MEMORY_SHARE * limit - need[0]) / per_page)


def build_engine(cfg, *, seed: int, slots: int, max_seq: int, page_size: int,
                 num_pages, mesh=None):
    """The engine ``repro.launch.serve`` builds for ``--cache-impl paged
    --fused-decode on`` (the policy rides in ``cfg``)."""
    from repro.launch import serve
    from repro.serving.telemetry import Telemetry

    return serve.Engine(cfg, slots=slots, max_seq=max_seq,
                        cache_impl="paged", page_size=page_size,
                        num_pages=num_pages, rng_seed=seed,
                        fused_decode=True, telemetry=Telemetry(), mesh=mesh)


def phase_compiled_steps(eng, *, chunk: int) -> dict:
    """Compile the two traces the continuous scheduler runs (T=chunk while
    a prefill is in flight, T=1 for pure decode) with the engine's own
    arrays.  Returns ``{trace: (compile seconds, paged-kernel calls, device
    bytes needed)}``."""
    import jax.numpy as jnp

    out = {}
    B = eng.slots
    tables = eng._device_block_tables()
    for name, T in (("mixed", chunk), ("decode", 1)):
        zeros = jnp.zeros((B,), jnp.int32)
        t0 = time.perf_counter()
        with eng._hints():
            compiled = eng._mixed_step.lower(
                eng.params, eng.cache, jnp.zeros((B, T), jnp.int32), zeros,
                zeros, tables, page_size=eng.page_size, key=eng._token_key,
                fused=eng.fused_decode).compile()
        m = compiled.memory_analysis()
        out[name] = (time.perf_counter() - t0,
                     compiled.as_text().count("tpu_custom_call"),
                     m.argument_size_in_bytes + m.output_size_in_bytes
                     - m.alias_size_in_bytes + m.temp_size_in_bytes)
    return out


def phase_serve(eng, queue, *, gen: int, chunk: int, seed: int):
    """Serve ``queue`` through the continuous scheduler (``serve.run``)."""
    from repro.launch import serve

    return serve.run(eng, [np.asarray(p) for p in queue], gen=gen,
                     seed=seed, quiet=True, scheduler="continuous",
                     chunk=chunk)


# --------------------------------------------------------------------------- #
# Phase: engine logits vs a plain float32 forward
# --------------------------------------------------------------------------- #
def _dense(w):
    import jax.numpy as jnp

    return jnp.asarray(w, jnp.float32)


def reference_logits(cfg, params, tokens):
    """Last-position logits of a plain float32 causal forward pass of a
    dense GQA decoder (RMSNorm with (1 + w) scales, split-half RoPE, QKV
    bias, SwiGLU, tied or untied unembedding), at highest matmul precision."""
    import jax
    import jax.numpy as jnp

    tokens = jnp.asarray(tokens, jnp.int32)
    S = tokens.shape[0]
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd

    def norm(x, w):
        x = x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + cfg.norm_eps)
        return x * (1.0 + _dense(w))

    half = hd // 2
    freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]

    def rope(x):
        a, b = x[..., :half], x[..., half:]
        return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)

    causal = jnp.tril(jnp.ones((S, S), bool))
    (blocks,) = params["blocks"]
    with jax.default_matmul_precision("highest"):
        x = _dense(params["embed"])[tokens]
        for i in range(cfg.n_layers):
            p = jax.tree.map(lambda a: a[i], blocks)
            a = p["attn"]
            h = norm(x, p["ln1"])
            q = (h @ _dense(a["wq"]) + _dense(a["bq"])).reshape(S, H, hd)
            k = (h @ _dense(a["wk"]) + _dense(a["bk"])).reshape(S, KV, hd)
            v = (h @ _dense(a["wv"]) + _dense(a["bv"])).reshape(S, KV, hd)
            q, k = rope(q), rope(k)
            k = jnp.repeat(k, H // KV, axis=1)
            v = jnp.repeat(v, H // KV, axis=1)
            s = jnp.einsum("shd,thd->hst", q, k) * hd ** -0.5
            s = jnp.where(causal, s, -jnp.inf)
            o = jnp.einsum("hst,thd->shd", jax.nn.softmax(s, -1), v)
            x = x + o.reshape(S, H * hd) @ _dense(a["wo"])
            f = p["ffn"]
            h = norm(x, p["ln2"])
            x = x + (jax.nn.silu(h @ _dense(f["w_gate"]))
                     * (h @ _dense(f["w_up"]))) @ _dense(f["w_down"])
        x = norm(x[-1], params["final_norm"])
        w = params.get("unembed")
        logits = x @ (_dense(w) if w is not None else _dense(params["embed"]).T)
    return np.asarray(logits[:cfg.vocab])


def phase_logits(eng, prompt, *, chunk: int) -> dict:
    """The engine's last-prompt-token logits (prefilled through its paged
    mixed step in a free slot) against :func:`reference_logits`."""
    slot = 0
    got = eng.tail_prefill([(slot, np.asarray(prompt), 0)], chunk=chunk)[slot]
    eng.release(slot)
    got = np.asarray(got[:eng.cfg.vocab], np.float64)
    want = reference_logits(eng.cfg, eng.params, prompt).astype(np.float64)
    return {"rel_l2": float(np.linalg.norm(got - want) / np.linalg.norm(want)),
            "top1_equal": bool(got.argmax() == want.argmax())}


# --------------------------------------------------------------------------- #
# Phase (--chips 4): TP=2 engine vs the single-device engine
# --------------------------------------------------------------------------- #
def phase_mesh(cfg, queue, *, seed: int, slots: int, max_seq: int,
               page_size: int, gen: int, chunk: int) -> dict:
    """Serve one seeded stream on a (1, 2) mesh and on one device, in this
    process, and compare token streams and final caches bit for bit."""
    import jax

    from repro.launch.mesh import make_production_mesh

    outs, caches, tok_s = {}, {}, {}
    for name, mesh in (("tp1", None),
                       ("tp2", make_production_mesh(shape=(1, 2)))):
        eng = build_engine(cfg, seed=seed, slots=slots, max_seq=max_seq,
                           page_size=page_size, num_pages=None, mesh=mesh)
        outs[name], stats = phase_serve(eng, queue, gen=gen, chunk=chunk,
                                        seed=seed)
        tok_s[name] = stats["tok_s"]
        caches[name] = jax.tree.leaves(jax.device_get(eng.cache))
        del eng
    first_diff = next((rid for rid in sorted(outs["tp1"])
                       if outs["tp1"][rid] != outs["tp2"].get(rid)), None)
    return {
        "tokens_equal": outs["tp1"] == outs["tp2"],
        "first_differing_request": first_diff,
        "cache_equal": len(caches["tp1"]) == len(caches["tp2"]) and all(
            np.array_equal(a, b) for a, b in zip(caches["tp1"], caches["tp2"])),
        "finished": {k: len(v) for k, v in outs.items()},
        "tok_s": tok_s,
    }


# --------------------------------------------------------------------------- #
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 runs only the --mesh 1x2 engine against the "
                         "single-device engine")
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--prompt-lens", default="192,384",
                    help="MIN,MAX prompt tokens, drawn per request")
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--chunk", type=int, default=16,
                    help="prefill tokens per slot per step")
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args(argv)

    _import_repro()
    device = require_tpu(args.chips)
    import jax

    from repro.caches import enable_compile_cache
    from repro.configs import get_config

    print(f"device: {device['kind']} x{device['count']} "
          f"({device['platform']})")
    print(f"compile cache: {enable_compile_cache()}")
    cache_events = collections.Counter()
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_events.update(
            [event.rsplit("/", 1)[-1]]
            if event.startswith("/jax/compilation_cache/") else []))
    cfg = get_config(args.arch, policy="serve_fp8_paged")
    print(f"model: {cfg.name} layers={cfg.n_layers} d_model={cfg.d_model} "
          f"heads={cfg.n_heads} kv_heads={cfg.n_kv_heads} "
          f"head_dim={cfg.hd} vocab={cfg.vocab} (weights from seed "
          f"{args.seed})")
    lo, hi = (int(s) for s in args.prompt_lens.split(","))
    queue = make_queue(cfg, seed=args.seed, requests=args.requests,
                       prompt_lens=(lo, hi))
    max_seq = hi + args.gen
    failures = []
    start = time.perf_counter()

    def phase(what: str) -> None:
        print(f"[{time.perf_counter() - start:.0f} s] phase {what}")

    def check(ok: bool, what: str) -> None:
        print(f"  {'PASS' if ok else 'FAIL'}: {what}")
        if not ok:
            failures.append(what)

    if args.chips == 4:
        phase("mesh: --mesh 1x2 engine vs single-device engine")
        r = phase_mesh(cfg, queue, seed=args.seed, slots=args.slots,
                       max_seq=max_seq, page_size=args.page_size,
                       gen=args.gen, chunk=args.chunk)
        print(f"  finished {r['finished']}, tok/s (informational) "
              f"{ {k: round(v, 1) for k, v in r['tok_s'].items()} }")
        check(r["finished"]["tp1"] == len(queue),
              f"single-device engine finished {r['finished']['tp1']}"
              f"/{len(queue)}")
        check(r["tokens_equal"], "TP=2 token streams equal TP=1 (first "
              f"differing request: {r['first_differing_request']})")
        check(r["cache_equal"], "TP=2 final KV cache equals TP=1 bitwise")
    else:
        phase("kernel: paged kernel vs impl='ref' "
              f"(tolerance {KERNEL_RTOL} of max|ref|)")
        r = phase_kernel(cfg, seed=args.seed, slots=args.slots, ctx=max_seq,
                         page_size=args.page_size, num_pages=4096)
        check(r["unfused_rel_err"] <= KERNEL_RTOL,
              f"unfused kernel rel err {r['unfused_rel_err']!r}")
        check(r["fused_rel_err"] <= KERNEL_RTOL,
              f"fused write+attend rel err {r['fused_rel_err']!r}")
        check(r["fused_cache_equal"], "fused kernel cache writes equal ref")

        limit = jax.devices()[0].memory_stats()["bytes_limit"]
        pages = deployment_pages(
            cfg, slots=args.slots, page_size=args.page_size, max_seq=max_seq,
            chunk=args.chunk, limit=limit)
        eng = build_engine(cfg, seed=args.seed, slots=args.slots,
                           max_seq=max_seq, page_size=args.page_size,
                           num_pages=pages)
        phase(f"engine: {pages} pages of {args.page_size} tokens "
              f"({eng.kv_cache_bytes() / 2**30:.2f} GiB of codes+scales, "
              f"{eng.kv_capacity_tokens()} tokens), {args.slots} slots")
        steps = phase_compiled_steps(eng, chunk=args.chunk)
        for name, (sec, n, need) in steps.items():
            print(f"  {name} step trace: compiled in {sec:.1f} s, needs "
                  f"{need / 2**30:.2f} of {limit / 2**30:.2f} GiB")
            check(n > 0, f"{name} step contains the paged kernel "
                  f"({n} tpu_custom_call)")

        phase(f"serve: {len(queue)} requests, prompts {lo}-{hi} "
              f"tokens, gen {args.gen}, continuous scheduler, chunk "
              f"{args.chunk}")
        t0 = time.perf_counter()
        outputs, stats = phase_serve(eng, queue, gen=args.gen,
                                     chunk=args.chunk, seed=args.seed)
        wall = time.perf_counter() - t0
        states = stats["terminal"]
        print(f"  sent {len(queue)}, finished {states.get('finished', 0)}, "
              f"failed {len(queue) - states.get('finished', 0)} "
              f"({states}); {stats['steps']} steps in {wall:.1f} s, "
              f"{stats['tok_s']:.1f} tok/s (informational)")
        check(len(outputs) == len(queue)
              and all(len(o) == args.gen for o in outputs.values()),
              f"all {len(queue)} requests finished with {args.gen} tokens")

        phase(f"logits: engine vs float32 forward, prompt of "
              f"{len(queue[0])} tokens (rel L2 tolerance {LOGITS_REL_L2})")
        r = phase_logits(eng, queue[0], chunk=args.chunk)
        check(r["rel_l2"] <= LOGITS_REL_L2,
              f"logits rel L2 {r['rel_l2']!r} (top-1 equal: "
              f"{r['top1_equal']})")

    print(f"[{time.perf_counter() - start:.0f} s] compile cache: "
          f"{cache_events['cache_hits']} hits, "
          f"{cache_events['cache_misses']} misses")
    if failures:
        print(f"chip_smoke: {len(failures)} check(s) failed", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
