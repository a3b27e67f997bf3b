"""The harness end to end on the CPU at a tiny size: a sound run comes
out correct, and a run with the timed path broken underneath comes out
not correct, once for each fault a one-chip serving cell can have."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny
from chipbench import harness


def run(tmp_path, seconds=2.0, trace=False, **kw):
    cell = tiny.make(tmp_path, **kw)
    return harness.run_cell(cell, 2**31 + 99, seconds, trace=trace,
                            t_process=0.0, device=tiny.CPU)


def test_sound_run_is_correct_and_reports_its_metrics(tmp_path):
    res, r = run(tmp_path)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {m["name"] for m in r.cell.end_to_end}
    assert {"tok_s", "itl_p99_ms", "setup_s"} <= set(res["metrics"])
    assert all(v["value"] > 0 for v in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"gap_max", "gap_mean"}
    assert r.compiles_in_window == 0


def test_backlog_run_opens_after_the_first_wave(tmp_path):
    res, r = run(tmp_path, arrivals="backlog", family="qwen3")
    assert res["correct"], res["checks"]
    assert all(rec.token_times and rec.token_times[0] <= r.t_open
               for rec in r.records[:4])


def test_traced_run_reports_the_host_side_layer_metrics(tmp_path):
    res, _ = run(tmp_path, trace=True)
    assert res["correct"], res["checks"]
    for name in ("gen_lag_p99_ms", "queue_wait_p50_s", "slot_occupancy",
                 "kv_fill_share", "host_ms_per_step"):
        assert name in res["metrics"], name
    # the CPU has no device plane: no device metric is reported
    for name in ("paged_attn_roofline", "device_idle_share", "step_mfu",
                 "decode_step_ms", "mixed_step_ms"):
        assert name not in res["metrics"], name
    assert "breakdown" in res


def _state_unchanged(orig):
    def step(self, tokens, lengths, n_new, *, sync=True):
        old = jax.tree.map(jnp.copy, self.cache)
        out = orig(self, tokens, lengths, n_new, sync=sync)
        self.cache = old
        return out
    return step


def _token_altered(orig):
    def step(self, tokens, lengths, n_new, *, sync=True):
        out = orig(self, tokens, lengths, n_new, sync=sync)
        return (np.roll(out, 1, -1) if isinstance(out, np.ndarray)
                else jnp.roll(out, 1, -1))
    return step


def _half_batch(orig):
    """The step leaves out the second half of its active lanes: their
    logits come back zero."""
    def step(self, tokens, lengths, n_new, *, sync=True):
        out = orig(self, tokens, lengths, n_new, sync=sync)
        active = np.flatnonzero(np.asarray(n_new) > 0)
        drop = active[len(active) // 2:]
        if isinstance(out, np.ndarray):
            out = out.copy()
            out[drop] = 0.0
            return out
        return out.at[drop].set(0.0)
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered,
                                   _half_batch])
def test_broken_timed_path_is_not_correct(tmp_path, monkeypatch, fault):
    from repro.launch.serve import Engine

    monkeypatch.setattr(Engine, "step_chunk", fault(Engine.step_chunk))
    res, _ = run(tmp_path)
    assert not res["correct"], res["checks"]
