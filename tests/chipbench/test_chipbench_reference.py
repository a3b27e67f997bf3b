"""The plain float32 reference against the serving engine, at a small
size on the CPU, for both configurations' architectures: weights made
from the seed are what the engine serves, and with full-precision KV
pages the engine's logits after a chunked prefill and a decode step agree
with the reference's forward pass."""
import json

import jax
import numpy as np
import pytest

import tiny
from chipbench import cells, harness


def _engine(tmp_path, family, policy):
    cell = tiny.make(tmp_path, family=family)
    cfg = json.loads((cell.root / "configs" / "tiny.json").read_text())
    cfg["program"]["policy"] = policy
    (cell.root / "configs" / "tiny.json").write_text(json.dumps(cfg))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cell = cells.load_cell("tiny.cell", bench, root=cell.root)
    server = harness.Server(cell, profile=False)
    server.build(7)
    return cell, server.eng


@pytest.mark.parametrize("family", ["qwen2", "qwen3"])
def test_weights_are_the_seeds(tmp_path, family):
    cell, eng = _engine(tmp_path, family, "serve_fp8_paged")
    ref = cells.reference("dense_gqa", cell.root)
    (blocks,) = eng.params["blocks"]
    for i in range(cell.model["num_hidden_layers"]):
        mine = ref.layer_f32(cell.model, 7, i)
        got = jax.tree.map(lambda a: np.asarray(a[i], np.float32), blocks)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(mine)):
            np.testing.assert_array_equal(a, np.asarray(b))
    top = ref.top_f32(cell.model, 7)
    V = cell.model["vocab_size"]
    np.testing.assert_array_equal(
        np.asarray(eng.params["embed"][:V], np.float32),
        np.asarray(top["embed"]))


@pytest.mark.parametrize("family", ["qwen2", "qwen3"])
def test_engine_logits_agree_with_the_reference(tmp_path, family):
    cell, eng = _engine(tmp_path, family, "train_bf16")
    ref = cells.reference("dense_gqa", cell.root)
    V = cell.model["vocab_size"]
    prompt = np.random.default_rng(3).integers(0, V, 37).astype(np.int32)
    got = np.asarray(eng.tail_prefill([(1, prompt, 0)], chunk=4)[1][:V])
    want = np.asarray(ref.logits(cell.model, 7, prompt))[-1]
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    # bf16 weights and activations against float32 read 0.006-0.014 on
    # both families; a wrong norm, bias, rope or head reads order 0.1-1
    assert rel < 3e-2, rel
    # one decode step of the next token, next to an idle slot
    tok = int(np.argmax(want))
    lengths = np.zeros(eng.slots, np.int32)
    n_new = np.zeros(eng.slots, np.int32)
    toks = np.zeros((eng.slots, 1), np.int32)
    lengths[1], n_new[1], toks[1, 0] = len(prompt), 1, tok
    eng.pool.ensure_capacity_batch(lengths + n_new)
    got = np.asarray(eng.step_chunk(toks, lengths, n_new)[1][:V])
    want = np.asarray(ref.logits(cell.model, 7,
                                 np.append(prompt, tok)))[-1]
    rel = np.linalg.norm(got - want) / np.linalg.norm(want)
    assert rel < 3e-2, rel
