"""Scheduler: how busy the read pipeline's loop thread (tpusnap-read-pipeline) is: the
restore_cpu counter's loop_s (the thread's CPU seconds from its start to its end) over its call_s
(the restore call's wall), summed over the restores of the window, in percent.  None where the
library has no such counter."""
from chipbench.metrics._common import phase


def read(run):
    p = phase(run, "restore_cpu")
    return 100.0 * p["loop_s"] / p["call_s"] if p and p.get("call_s") and "loop_s" in p else None
