"""What the traced slice says about the model step and the paged kernel,
by name: the step program (``Model.step_paged``, jitted by the engine)
and the Pallas kernel (``kernels/paged_attention.py``, called from
``_fused_decode_write_attend``)
as they appear in the device trace."""
from __future__ import annotations

import re
from typing import Callable, Optional

from . import costs

STEP_PROGRAM = r"step_paged"
# the Pallas call of the fused write+attend (or of the plain paged
# attention), as its custom call appears in the device trace
KERNEL = (r"^%?_(fused_decode_write_attend|paged_decode_attention)[.\d]* = "
          r".*tpu_custom_call")


def traced_cost(run) -> dict:
    """Summed needs (``costs.step_cost``) of the traced steps."""
    out = {"kernel_bytes": 0, "kernel_ops": 0, "tokens": 0, "flops": 0}
    for s in run.traced_steps():
        if s.T == 0:
            continue
        c = costs.step_cost(s.lengths, s.n_new, s.emitted, run.dims,
                            run.page_size)
        for k in out:
            out[k] += c[k]
    return out


def kernel_seconds(run) -> Optional[float]:
    if run.trace is None:
        return None
    return run.trace.matching_seconds(KERNEL) or None


def mean_program_ms(run, want: Callable[[int], bool]) -> Optional[float]:
    """Mean device time of the step program's runs whose step fed ``T``
    tokens per lane with ``want(T)``.  The runs are matched to the traced
    steps in order; a trace whose count of step programs differs from the
    count of model steps is not read."""
    if run.trace is None:
        return None
    rx = re.compile(STEP_PROGRAM)
    runs = sorted((m for m in run.trace.modules if rx.search(m.name)),
                  key=lambda m: m.start_ns)
    steps = [s for s in run.traced_steps() if s.T > 0]
    if not runs or len(runs) != len(steps):
        return None
    d = [m.dur_ns for m, s in zip(runs, steps) if want(s.T)]
    return sum(d) / len(d) * 1e-6 if d else None
