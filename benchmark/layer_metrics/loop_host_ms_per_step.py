"""The driver thread's own work per decode step: the seconds of the five
phases that do not wait (admission, chunk and step dispatch, activation,
emit + retire) over the window's steps.  ``idle_wait``, ``chunk_fetch`` and
``step_fetch`` wait for work or for the device and are left out."""
from benchmark.layer_metrics import _slot_loop

HOST_PHASES = ("admit", "chunk_dispatch", "activate", "step_dispatch", "retire")


def compute(ctx):
    c = _slot_loop.stats(ctx)
    phase_s = c.get("phase_s")
    if not phase_s or not c.get("steps"):
        return None
    return 1e3 * sum(phase_s[k] for k in HOST_PHASES) / c["steps"]
