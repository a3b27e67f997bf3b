"""A throwaway cell made of files alone, at a size a CPU test can serve:
a copy of the benchmark's directory with one more configuration, mix and
cell file, and a ``BENCHMARK.json`` that names them."""
import json
import pathlib
import shutil

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())


# Limits for the tiny model, from CPU readings (PR 12): sound runs read a
# widest gap of at most 0.74 and a mean gap of at most 0.08 over seeds
# 1, 2, 3 and 2**31 + 99 of both families and both arrival kinds; a step
# that keeps its cache, an altered token or a dropped half batch read
# widest gaps of 2.5 and more and mean gaps of 0.6 and more.
def make(tmp: pathlib.Path, *, family: str = "qwen2",
         arrivals: str = "poisson", limits=None):
    from chipbench import cells

    root = tmp / "chipbench"
    shutil.copytree(ROOT / "chipbench", root,
                    ignore=shutil.ignore_patterns("__pycache__"))
    qwen2 = family == "qwen2"
    (root / "configs" / "tiny.json").write_text(json.dumps({
        "name": "tiny", "source": "test",
        "hidden_size": 64, "intermediate_size": 128,
        "num_attention_heads": 4, "num_hidden_layers": 2,
        "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256,
        "rms_norm_eps": 1e-6, "rope_theta": 10000.0,
        "tie_word_embeddings": qwen2, "attention_bias": qwen2,
        "reduced": {}, "assumed": {"qk_norm": not qwen2},
        "program": {"arch": "qwen2-0.5b" if qwen2 else "qwen3-14b",
                    "policy": "serve_fp8_paged"},
        "reference": "dense_gqa"}))
    (root / "traffic" / "tiny-mix.json").write_text(json.dumps({
        "arrivals": arrivals,
        "prompt_tokens": {"dist": "uniform", "min": 4, "max": 40},
        "output_tokens": {"dist": "uniform", "min": 4, "max": 12}}))
    (root / "cells" / "tiny.cell.json").write_text(json.dumps({
        "slots": 4, "chunk": 4, "page_size": 16,
        "rate_per_s": 3.0, "requests": 6, "warmup_s": 0.5,
        "trace_seconds": 1.0,
        "check": {"requests": 3, "limits": limits if limits is not None
                  else {"gap_max": 2.0, "gap_mean": 0.5}}}))
    bench = json.loads(json.dumps(BENCH))
    bench["configs"].append({"name": "tiny", "source": "test",
                             "file": "chipbench/configs/tiny.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "tiny.cell", "config": "tiny",
                               "traffic": "tiny-mix", "chips": 1,
                               "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.cell")
    bench["end_to_end"] += [dict(m, workloads=["tiny.cell"])
                             for m in OPEN_LOOP_END_TO_END]
    bench["per_layer"] += [dict(m, workloads=["tiny.cell"])
                           for m in OPEN_LOOP_PER_LAYER]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return cells.load_cell("tiny.cell", bench, root=root)


# The readers of an open-loop cell (chat-short, held out of
# BENCHMARK.json; see PERF.md), so that they stay tested.
OPEN_LOOP_END_TO_END = [
    {"name": "itl_p99_ms", "unit": "ms", "better": "lower", "bound": 0.25,
     "source": "host_clock"},
    {"name": "ttft_p50_s", "unit": "s", "better": "lower", "bound": 0.25,
     "source": "host_clock"}]
OPEN_LOOP_PER_LAYER = [
    {"name": n, "unit": u, "better": "lower", "source": src, "layer": layer,
     "moves": "ttft_p50_s"}
    for n, u, src, layer in (
        ("gen_lag_p99_ms", "ms", "host_clock", "load generator"),
        ("queue_wait_p50_s", "s", "host_clock", "scheduler"),
        ("slot_occupancy", "%", "program_counter", "scheduler"),
        ("mixed_step_ms", "ms", "device_trace", "model step"))]

CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
