"""Time per step in the window spent copying the step's logits from the
device to the host: the program's ``fetch`` spans (inside ``sync``, after
the ``wait`` for the device) starting in the window, summed, over the
steps in the window (ms)."""


def read(run):
    steps = len(run.window_steps())
    fetch = [e["d"] for e in run.spans if e["name"] == "fetch"
             and run.t_open <= e["t"] < run.t_close]
    if steps == 0 or not fetch:
        return None
    return sum(fetch) / steps * 1e3
