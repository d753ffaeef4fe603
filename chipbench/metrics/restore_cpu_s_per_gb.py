"""Orchestration: the host CPU a restored GB costs: the restore_cpu counter's s (the process's CPU
seconds over each restore call, every thread of it) over its bytes (the payload bytes the calls'
plans read), in CPU seconds per 1e9 bytes, over the restores of the window.  None where the library
has no such counter."""
from chipbench.metrics._common import phase


def read(run):
    p = phase(run, "restore_cpu")
    return p["s"] / (p["bytes"] / 1e9) if p and p.get("bytes") else None
