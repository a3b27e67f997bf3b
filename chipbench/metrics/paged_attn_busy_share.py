"""The paged kernel's device time over the device's busy time in the
traced slice (%)."""
from chipbench import steps


def read(run):
    t = steps.kernel_seconds(run)
    if not t or run.trace is None or run.trace.busy_s <= 0:
        return None
    return 100.0 * t / run.trace.busy_s
