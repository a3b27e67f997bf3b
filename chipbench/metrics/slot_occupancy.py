"""Share of slot-steps in the window that carried a request: the
scheduler's occupied_slot_steps over steps x slots, window deltas (%)."""
from chipbench import stats


def read(run):
    steps = stats.counter_delta(run.counters_open, run.counters_close,
                                "steps")
    if steps <= 0:
        return None
    occ = stats.counter_delta(run.counters_open, run.counters_close,
                              "occupied_slot_steps")
    return 100.0 * occ / (steps * run.slots)
