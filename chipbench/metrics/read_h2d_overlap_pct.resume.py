"""Scheduler: how much of the shorter of a restore's two streaming stages the longer one hides: the
restore_overlap counter's s (seconds in which a storage read and an H2D dispatch or landing were
both under way, by the call's own intervals) over the smaller of its reads_s and h2d_s (each
stage's wall inside the call), summed over the restores of the window, in percent.  None where the
library has no such counter or one of the two stages never ran."""
from chipbench.metrics._common import phase


def read(run):
    p = phase(run, "restore_overlap")
    shorter = min(p.get("reads_s", 0), p.get("h2d_s", 0)) if p else 0
    return 100.0 * p["s"] / shorter if shorter > 0 else None
