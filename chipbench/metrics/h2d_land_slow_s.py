"""H2D: seconds of the window spent in landings that stalled: the h2d_land_slow counter's s (a
landing of half a second and more at under 0.5 GB/s, a tenth of the slowest rate a healthy landing
was measured at), summed over the window, not per restore: one stall is what moves a run.  0.0
where landings were made (h2d_land) and none was slow; None where none was made, or where the
library is from before the counter: one that has it also records restore_overlap once a restore."""
from chipbench.metrics._common import phase


def read(run):
    if not (phase(run, "h2d_land") and phase(run, "restore_overlap")):
        return None
    p = phase(run, "h2d_land_slow")
    return p["s"] if p else 0.0
