"""The control comes out not correct: the reference computed one
precision lower than the configuration states (FP8 weights and
activations, int4 KV pages) and read at the same positions of the same
served prompts and tokens gives a mean gap above the limit that sound
runs of the program stay under.  At a size a CPU test can serve
(hidden 512, 6 layers); on the chip the same reading is taken at each
cell's own size (``chipbench/control.py``)."""
import json

import tiny
from chipbench import cells, harness

# CPU readings (PR 12), this size, seeds 1-3: the program's mean gap
# 0.0038-0.0065 and widest gap 0.16-0.19; the control's mean gap
# 0.046-0.060 and widest gap 0.49-0.67.
LIMITS = {"gap_max": 0.4, "gap_mean": 0.02}


def test_control_fails_where_the_program_passes(tmp_path):
    cell = tiny.make(tmp_path, arrivals="backlog", limits=LIMITS)
    cfg = json.loads((cell.root / "configs" / "tiny.json").read_text())
    cfg.update(hidden_size=512, intermediate_size=1024,
               num_attention_heads=8, num_key_value_heads=2, head_dim=64,
               vocab_size=4096, num_hidden_layers=6)
    (cell.root / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mix = json.loads((cell.root / "traffic" / "tiny-mix.json").read_text())
    mix["prompt_tokens"] = {"dist": "uniform", "min": 64, "max": 128}
    mix["output_tokens"] = {"dist": "uniform", "min": 40, "max": 60}
    (cell.root / "traffic" / "tiny-mix.json").write_text(json.dumps(mix))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    cell = cells.load_cell("tiny.cell", bench, root=cell.root)
    res, run = harness.run_cell(cell, 2, 8.0, trace=False, t_process=0.0,
                                device=tiny.CPU)
    assert res["correct"], res["checks"]
    sample = harness.check_sample(run, 4)
    _, control = harness.compare(run, sample, control=True)
    got = harness.numbers(control)
    assert got["gap_mean"] > LIMITS["gap_mean"], got
