"""Routing assignments to the experts this chip holds, per step of the
window, per expert layer, per held expert: the load each held expert's
matmuls see.  The program counts them on the device for each step
(``moe_assignments_total{expert=held|absent}``) and hands the step's
counts over with its logits, as the ``moe_held`` argument of the
``fetch`` span; the window's spans are summed.  Nothing to read for a
program that reports no such counts."""


def _expert_layers(c: dict) -> int:
    first = c.get("first_k_dense_replace", 0)
    freq = c.get("moe_layer_freq", 1)
    return sum(1 for i in range(c["num_hidden_layers"])
               if i >= first and i % freq == 0)


def read(run):
    c = run.cell.model
    if "n_routed_experts" not in c:
        return None
    steps = len(run.window_steps())
    held = [int(e["args"]["moe_held"]) for e in run.spans
            if e["name"] == "fetch" and "moe_held" in e.get("args", {})
            and run.t_open <= e["t"] < run.t_close]
    if steps == 0 or not held:
        return None
    return sum(held) / steps / _expert_layers(c) / c["n_routed_experts"]
