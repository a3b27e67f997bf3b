"""Mean device time of the model step compiled for T=chunk (a prefill
chunk in flight), from the traced slice (ms)."""
from chipbench import steps


def read(run):
    return steps.mean_program_ms(run, lambda T: T > 1)
