"""The DeepSeek-V2-Lite configuration: its file agrees with the program's
preset; the plain reference (``references/mla_moe.py``) against the
serving engine at a tiny CPU size, with a planted fault that the check
catches; and the two metrics the configuration brings, read from
scripted runs."""
import json
import pathlib
import types

import jax
import numpy as np
import pytest

import tiny
from chipbench import cells, harness, peaks, steps

ROOT = pathlib.Path(__file__).resolve().parents[2]
NAME = "deepseek-v2-lite-ep4"
CONFIG = json.loads((ROOT / "chipbench" / "configs" / f"{NAME}.json")
                    .read_text())


def test_configuration_file_agrees_with_the_program_preset():
    from repro.configs import get_config

    c = CONFIG
    cfg = get_config(c["program"]["arch"])
    # the share: experts 0-15 held, the router at its published 64
    assert c["reduced"] == {"n_routed_experts": 64}
    share = c["held_experts"]
    assert cfg.held_range == (share["first"], c["n_routed_experts"]) == (0, 16)
    assert cfg.n_experts == share["router_width"] == 64
    published = {
        "num_hidden_layers": cfg.n_layers, "hidden_size": cfg.d_model,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.d_ff, "vocab_size": cfg.vocab,
        "kv_lora_rank": cfg.kv_lora_rank,
        "qk_nope_head_dim": cfg.qk_nope_dim,
        "qk_rope_head_dim": cfg.qk_rope_dim, "v_head_dim": cfg.v_head_dim,
        "moe_intermediate_size": cfg.moe_d_ff,
        "n_shared_experts": cfg.n_shared_experts,
        "num_experts_per_tok": cfg.top_k,
        "first_k_dense_replace": cfg.first_dense,
        "moe_layer_freq": cfg.moe_period,
        "norm_topk_prob": cfg.norm_topk_prob, "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
        "attention_bias": cfg.qkv_bias, "hidden_act": cfg.act_fn,
    }
    for k, v in published.items():
        assert c[k] == v, k
    y, py = c["rope_scaling"], cfg.yarn
    assert y["type"] == "yarn"
    assert (y["factor"], y["original_max_position_embeddings"],
            y["beta_fast"], y["beta_slow"], y["mscale"],
            y["mscale_all_dim"]) == (
        py.factor, py.original_max_position_embeddings, py.beta_fast,
        py.beta_slow, py.mscale, py.mscale_all_dim)
    assert (c["scoring_func"], c["topk_method"], c["routed_scaling_factor"],
            c["q_lora_rank"], c["n_group"], c["topk_group"]) == (
        "softmax", "greedy", 1, None, 1, 1)
    assert "head_dim" not in c  # harness._FIELDS would override 192
    # the harness's published-key overrides leave the preset as it is
    cell = cells.load_cell(f"{NAME}.decode-long")
    assert harness.program_config(cell) == get_config(
        NAME, policy=c["program"]["policy"])


# --------------------------------------------------------------------------- #
# The reference against the engine, at a tiny size
# --------------------------------------------------------------------------- #
# Limits for the tiny MLA cell, from CPU readings: sound runs read a
# widest gap of 0.30-0.86 and a mean gap of 0.061-0.182 over seeds 1-3 and
# 2**31 + 99; with the latent pages never written, 3.77-5.30 and 2.51-2.91.
TINY_LIMITS = {"gap_max": 2.0, "gap_mean": 0.6}


def _tiny_mla(tmp_path, policy="serve_fp8_paged", **kw):
    """The benchmark's tiny cell with the DeepSeek-V2 configuration at a
    tiny hidden size, depth and vocabulary; the latent, rope, expert and
    router widths are the published ones, which the program's preset
    keeps (the harness overrides only the keys it maps)."""
    cell = tiny.make(tmp_path, limits=TINY_LIMITS, **kw)
    c = dict(CONFIG, hidden_size=64, num_attention_heads=4,
             num_key_value_heads=4, num_hidden_layers=3, vocab_size=256,
             intermediate_size=128)
    c["program"] = dict(c["program"], policy=policy)
    (cell.root / "configs" / "tiny.json").write_text(json.dumps(c))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    return cells.load_cell("tiny.cell", bench, root=cell.root)


def _engine(tmp_path, policy):
    cell = _tiny_mla(tmp_path, policy)
    server = harness.Server(cell, profile=False)
    server.build(7)
    return cell, server.eng


def test_weights_are_the_seeds(tmp_path):
    cell, eng = _engine(tmp_path, "serve_fp8_paged")
    ref = cells.reference("mla_moe", cell.root)
    (blocks,) = eng.params["blocks"]
    layers = [eng.params["prefix"][0]] + [
        jax.tree.map(lambda a, i=i: a[i], blocks)
        for i in range(cell.model["num_hidden_layers"] - 1)]
    for i, got in enumerate(layers):
        want = ref.layer_f32(cell.model, 7, i)
        assert (jax.tree.structure(got) == jax.tree.structure(want))
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b))
    assert blocks["ffn"]["w_gate"].shape[1] == 16  # the held experts
    assert blocks["ffn"]["router"].shape[-1] == 64  # the whole router


@pytest.mark.parametrize("policy,tol", [("train_bf16", 3e-2),
                                        ("serve_fp8_paged", 0.3)])
def test_engine_logits_agree_with_the_reference(tmp_path, policy, tol):
    """A chunked prefill, then one paged decode step next to an idle slot,
    against the reference's full forward.  Tolerances, from CPU readings
    of the last prefill position: bfloat16 weights and activations
    with float pages read 0.012, and a softmax scale without YaRN's
    mscale**2, plain rope frequencies or renormalized gates read 0.47,
    0.48 and 0.12.  FP8 E5M2 latent pages (2 mantissa bits on the values
    too) read 0.175, as the tiny GQA models' FP8 pages do (0.13 and 0.20);
    the missing mscale or YaRN still read 0.45 and 0.47."""
    cell, eng = _engine(tmp_path, policy)
    ref = cells.reference("mla_moe", cell.root)
    V = cell.model["vocab_size"]
    prompt = np.random.default_rng(3).integers(0, V, 37).astype(np.int32)
    got = np.asarray(eng.tail_prefill([(1, prompt, 0)], chunk=4)[1][:V])
    want = np.asarray(ref.logits(cell.model, 7, prompt))[-1]
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < tol, rel
    tok = int(np.argmax(want))
    lengths = np.zeros(eng.slots, np.int32)
    n_new = np.zeros(eng.slots, np.int32)
    toks = np.zeros((eng.slots, 1), np.int32)
    lengths[1], n_new[1], toks[1, 0] = len(prompt), 1, tok
    eng.pool.ensure_capacity_batch(lengths + n_new)
    got = np.asarray(eng.step_chunk(toks, lengths, n_new)[1][:V])
    want = np.asarray(ref.logits(cell.model, 7, np.append(prompt, tok)))[-1]
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < tol, rel


def _latents_never_written(monkeypatch):
    """The fault: the latent pages keep what they held before each write
    (the step still sees its own new row, which rides in the kernel)."""
    from repro import numerics

    orig = numerics.kv_fused_write_attend

    def fused(*a, **kw):
        out, kp, ks, vp, vs = orig(*a, **kw)
        if a[4].ndim == 3:  # an MLA layer's [P, page, L] latent pages
            vp, vs = a[4], a[6]
        return out, kp, ks, vp, vs

    monkeypatch.setattr(numerics, "kv_fused_write_attend", fused)


@pytest.mark.parametrize("fault", [None, _latents_never_written],
                         ids=["sound", "latents-never-written"])
def test_check_catches_latent_pages_never_written(tmp_path, monkeypatch,
                                                  fault):
    if fault is not None:
        fault(monkeypatch)
    cell = _tiny_mla(tmp_path)
    res, _ = harness.run_cell(cell, 2**31 + 99, 2.0, trace=False,
                              t_process=0.0, device=tiny.CPU)
    assert res["correct"] == (fault is None), res["checks"]


# The control at a size where the latent pages carry most of the error
# (hidden 512, the published 16 heads, 6 layers).  CPU readings at this
# size, seeds 1-3: the program's mean gap 0.21-0.26; the control's
# 0.76-0.96.
CONTROL_LIMITS = {"gap_mean": 0.45}


def _mid_mla(tmp_path, limits):
    cell = _tiny_mla(tmp_path, arrivals="backlog")
    c = json.loads((cell.root / "configs" / "tiny.json").read_text())
    c.update(hidden_size=512, num_attention_heads=16,
             num_key_value_heads=16, num_hidden_layers=6, vocab_size=4096,
             intermediate_size=1024)
    (cell.root / "configs" / "tiny.json").write_text(json.dumps(c))
    mix = json.loads((cell.root / "traffic" / "tiny-mix.json").read_text())
    mix["prompt_tokens"] = {"dist": "uniform", "min": 64, "max": 128}
    mix["output_tokens"] = {"dist": "uniform", "min": 40, "max": 60}
    (cell.root / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    spec = json.loads((cell.root / "cells" / "tiny.cell.json").read_text())
    spec["check"]["limits"] = limits
    (cell.root / "cells" / "tiny.cell.json").write_text(json.dumps(spec))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    return cells.load_cell("tiny.cell", bench, root=cell.root)


def test_control_fails_where_the_program_passes(tmp_path):
    """The reference one precision lower (FP8 E4M3 matmul operands, int4
    latents, rope keys and query) read at the same positions of the same
    served tokens: its mean gap lies above the limit that a sound run of
    the program stays under, as for the dense cells
    (``test_chipbench_control.py``)."""
    cell = _mid_mla(tmp_path, CONTROL_LIMITS)
    res, run = harness.run_cell(cell, 2, 8.0, trace=False, t_process=0.0,
                                device=tiny.CPU)
    assert res["correct"], res["checks"]
    _, control = harness.compare(run, harness.check_sample(run, 4),
                                 control=True)
    got = harness.numbers(control)
    assert got["gap_mean"] > CONTROL_LIMITS["gap_mean"], got


# --------------------------------------------------------------------------- #
# The configuration's metrics, from scripted runs
# --------------------------------------------------------------------------- #
class _Trace:
    def __init__(self, kernel_s):
        self.kernel_s = kernel_s

    def matching_seconds(self, pattern):
        assert pattern == steps.KERNEL
        return self.kernel_s


def _run(model, *, step_list=(), spans=(), kernel_s=None, window=(0.0, 10.0)):
    st = [types.SimpleNamespace(**s) for s in step_list]
    return types.SimpleNamespace(
        cell=types.SimpleNamespace(model=model), page_size=16,
        peaks=peaks.peaks_for("TPU v5 lite"), spans=list(spans),
        trace=None if kernel_s is None else _Trace(kernel_s),
        t_open=window[0], t_close=window[1],
        traced_steps=lambda: st, window_steps=lambda: st)


def test_mla_attn_roofline_by_hand():
    """One T=1 step of two lanes holding 15 and 16 tokens: they walk 1 and
    2 pages of 16 rows of 576 B (latent 512 + rope key 64) and 2 scales
    each, read 16 x 576 query codes, write a 576 B row and a 16 x 512 f32
    output: 51784 + 61008 B a layer, 27 layers; 16 + 17 positions of 2 x
    16 x 576 + 2 x 16 x 512 ops.  The bytes bound it: 3045384 B at 819
    GB/s in 1 ms of kernel time."""
    read = cells.metric_reader("mla_attn_roofline")
    model = dict(CONFIG)
    step = dict(T=1, lengths=np.array([15, 16, 0]),
                n_new=np.array([1, 1, 0]))
    run = _run(model, step_list=[step], kernel_s=1e-3)
    want_bytes = 27 * (51784 + 61008)
    want_ops = 27 * 33 * (2 * 16 * 576 + 2 * 16 * 512)
    assert want_ops / 393e12 < want_bytes / 819e9
    assert read(run) == pytest.approx(100 * want_bytes / 819e9 / 1e-3)
    assert read(_run(model, step_list=[step])) is None  # no trace
    gqa = cells.load_cell("qwen2-0.5b.decode-long").model
    assert read(_run(gqa, step_list=[step], kernel_s=1e-3)) is None


def test_moe_expert_load_on_a_scripted_run():
    """Four window steps of 64 lanes, each routed to 6 of 64 experts in 26
    expert layers, a quarter of them to the 16 held here: 6 assignments a
    held expert per layer per step.  A fetch span outside the window, and
    one without counts, are not read; a program that hands no counts over
    reads nothing."""
    read = cells.metric_reader("moe_expert_load")
    held = 64 * 6 * 26 // 4
    spans = [{"name": "fetch", "t": 1.0 + i, "d": 0.01,
              "args": {"moe_held": str(held), "moe_absent": str(3 * held)}}
             for i in range(4)]
    spans += [{"name": "fetch", "t": 20.0, "d": 0.01,
               "args": {"moe_held": "99999", "moe_absent": "0"}},
              {"name": "fetch", "t": 2.5, "d": 0.01}]
    step_list = [dict(T=1)] * 4
    assert read(_run(dict(CONFIG), step_list=step_list,
                     spans=spans)) == pytest.approx(6.0)
    bare = [dict(s, args={}) for s in spans]
    assert read(_run(dict(CONFIG), step_list=step_list, spans=bare)) is None
    gqa = cells.load_cell("qwen2-0.5b.decode-long").model
    assert read(_run(gqa, step_list=step_list, spans=spans)) is None
