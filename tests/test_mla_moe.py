"""DeepSeek-V2's serving path at small widths on the CPU: the latent pages
in the pool, dropless routing over a share of the experts, YaRN and the
published gates."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.launch import serve
from repro.models import moe
from repro.models.attention import mla_softmax_scale
from repro.models.layers import gated_mlp, yarn_mscale, yarn_rope


def _cfg(arch="deepseek-v2-lite-ep4", **kw):
    return dataclasses.replace(
        get_config(arch, smoke=True, policy="serve_fp8_paged"), **kw)


def test_paged_cache_holds_no_per_slot_mla_array():
    """Every MLA layer's state is two page arrays of the pool, rope keys
    [P, page, rope] and latents [P, page, lora], and nothing per slot."""
    cfg = _cfg()
    eng = serve.Engine(cfg, slots=3, max_seq=32, cache_impl="paged",
                       page_size=8)
    P = eng.pool.num_pages
    entries = [(e, False) for e in eng.cache["prefix"]] + [
        (e, True) for e in eng.cache["blocks"]]
    for e, stacked in entries:
        assert set(e) == {"self"}
        shapes = {k: v.shape[1:] if stacked else v.shape
                  for k, v in e["self"].items()}
        assert shapes == {"kp": (P, 8, cfg.qk_rope_dim),
                          "vp": (P, 8, cfg.kv_lora_rank),
                          "ks": (P,), "vs": (P,)}
    # the page counter sees every MLA layer
    assert eng._paged_layers == cfg.n_layers


def test_yarn_matches_the_written_formula_at_published_values():
    """DeepSeek-V2-Lite's rope_scaling (factor 40, original 4096, beta
    32/1, mscale = mscale_all_dim = 0.707) on its 64 rope lanes: the
    correction dimensions are floor(10.47) = 10 and ceil(22.5) = 23, so
    frequencies 0-10 keep theta^(-i/32), 23-31 are divided by 40, and the
    ones between blend linearly; cos and sin keep unit amplitude; the
    softmax scale is 192^-0.5 * (0.1 * 0.707 * ln 40 + 1)^2."""
    cfg = get_config("deepseek-v2-lite-16b")
    y = cfg.yarn
    freqs, amp = yarn_rope(64, cfg.rope_theta, y)
    extra = 10000.0 ** (-np.arange(32) / 32)
    ramp = np.clip((np.arange(32) - 10) / 13, 0, 1)
    want = extra * (1 - ramp) + extra / 40 * ramp
    np.testing.assert_allclose(freqs, want, rtol=1e-6)
    np.testing.assert_allclose(freqs[:11], extra[:11], rtol=1e-6)
    np.testing.assert_allclose(freqs[23:], extra[23:] / 40, rtol=1e-6)
    assert amp == 1.0
    m = 0.1 * 0.707 * math.log(40) + 1
    assert yarn_mscale(40, 0.707) == pytest.approx(m)
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert mla_softmax_scale(cfg) == pytest.approx(192 ** -0.5 * m * m)


@pytest.mark.parametrize("norm", [False, True])
def test_norm_topk_prob_leaves_the_gates_as_the_router_gave_them(norm):
    cfg = _cfg("deepseek-v2-lite-16b", norm_topk_prob=norm)
    assert get_config("deepseek-v2-lite-16b").norm_topk_prob is False
    assert get_config("granite-moe-3b-a800m").norm_topk_prob is True
    p = moe.moe_init(jax.random.PRNGKey(0), cfg)
    p["router"] = jax.random.normal(jax.random.PRNGKey(2), p["router"].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model))
    _, probs, gates, ids = moe._route(p, x, cfg)
    chosen = np.take_along_axis(np.asarray(probs), np.asarray(ids), -1)
    if norm:
        np.testing.assert_allclose(np.asarray(gates).sum(-1), 1.0, rtol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(gates), chosen)
        sums = np.asarray(gates).sum(-1)
        assert np.all(sums <= 1.0) and np.any(sums < 0.99)


def test_expert_shares_sum_to_the_whole_layer():
    """Four chips' shares of 8 routed experts (2 each) over the same
    tokens: their parts, with the shared experts (computed alike on every
    chip) counted once, add up to the layer that holds all 8."""
    whole = _cfg("deepseek-v2-lite-16b")
    assert whole.n_experts == 8 and whole.held_range == (0, 8)
    p = moe.moe_init(jax.random.PRNGKey(0), whole)
    p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
    p["router"] = jax.random.normal(jax.random.PRNGKey(2), p["router"].shape)
    x = jax.random.normal(jax.random.PRNGKey(1), (6, 3, whole.d_model))
    want, counts = moe.moe_serve(p, x, whole)
    assert int(counts["moe_absent"]) == 0
    shared = gated_mlp(x, p["shared"], whole.policy, whole.act_fn)
    total, held = -3 * shared, 0
    for first in range(0, 8, 2):
        share = dataclasses.replace(whole, held_experts=(first, 2))
        ps = dict(p, **{k: p[k][first:first + 2]
                        for k in ("w_gate", "w_up", "w_down")})
        out, c = moe.moe_serve(ps, x, share)
        total, held = total + out, held + int(c["moe_held"])
        assert int(c["moe_held"]) + int(c["moe_absent"]) == 18 * whole.top_k
    assert held == 18 * whole.top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_dropless_request_logits_do_not_depend_on_its_batch():
    """A request's logits alone and beside eleven copies of itself, whose
    tokens all route to the same experts: 12 tokens per chosen expert,
    past the old dispatch's capacity of 8 a group, and still identical."""
    cfg = _cfg("deepseek-v2-lite-16b")
    assert moe.capacity(12, cfg.top_k, cfg.n_experts,
                        cfg.capacity_factor) < 12
    prompt = np.random.default_rng(5).integers(0, cfg.vocab, 9)

    def last_logits(n):
        """The request in the last lane, with ``n - 1`` copies before it
        (a capacity dispatch keeps the first 8 of an expert's tokens)."""
        eng = serve.Engine(cfg, slots=12, max_seq=16, cache_impl="paged",
                           page_size=8, stochastic_kv=False)
        toks = np.zeros((12, 9), np.int32)
        toks[12 - n:] = prompt
        n_new = np.zeros(12, np.int32)
        n_new[12 - n:] = 9
        eng.pool.ensure_capacity_batch(n_new)
        return np.asarray(eng.step_chunk(toks, np.zeros(12, np.int32),
                                         n_new))[-1]

    np.testing.assert_array_equal(last_logits(1), last_logits(12))


def test_step_counts_routing_of_active_lanes_only():
    """The mixed step's counts cover the valid tokens of the active lanes:
    every expert layer routes each to top_k experts, held or absent."""
    cfg = _cfg()
    eng = serve.Engine(cfg, slots=3, max_seq=16, cache_impl="paged",
                       page_size=8)
    toks = np.random.default_rng(0).integers(0, cfg.vocab, (3, 4))
    n_new = np.array([4, 0, 2], np.int32)
    eng.pool.ensure_capacity_batch(n_new)
    eng.step_chunk(toks.astype(np.int32), np.zeros(3, np.int32), n_new)
    c = {k[1][0][1]: v.value for k, v in eng.tel._counters.items()
         if k[0] == "moe_assignments_total"}
    layers = sum(cfg.is_moe_layer(i) for i in range(cfg.n_layers))
    assert c["held"] + c["absent"] == 6 * cfg.top_k * layers
    assert c["held"] > 0 and c["absent"] > 0
    fetch = [e for e in eng.tel.events if e["name"] == "fetch"]
    assert fetch[-1]["args"] == {"moe_held": str(int(c["held"])),
                                 "moe_absent": str(int(c["absent"]))}
