"""The program's ``compile`` spans on the harness clock.

The program (``repro.serving.telemetry``) records each backend compile
and each read of JAX's persistent compilation cache as a ``compile``
span of its process registry, on that registry's own clock.  The
registry's wall-clock anchor (``t0_wall_ns``) and the wall clock now put
each span on ``time.perf_counter``, the clock of a ``harness.Run``.  A
program that records no compile spans (no such listener) gives None."""
from __future__ import annotations

import sys
import time
from typing import List, Optional, Tuple

PROGRAM = "repro.serving.telemetry"


def spans() -> Optional[List[Tuple[float, float]]]:
    """(start, end) of every compile span of the process so far, on the
    harness clock; None when the program records none."""
    tel = sys.modules.get(PROGRAM)
    if tel is None or not hasattr(tel, "watch_compiles"):
        return None
    reg = tel.default_registry()
    # harness clock = wall clock + (perf_counter - wall clock), both now
    base = reg.t0_wall_ns * 1e-9 + (time.perf_counter() - time.time())
    return [(base + e["ts"] * 1e-6, base + (e["ts"] + e["dur"]) * 1e-6)
            for e in reg.events
            if e.get("ph") == "X" and e["name"] == "compile"]
