"""Planning: phase_stats plan_read wall per restore of the window."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    p = phase(run, "plan_read")
    return p["wall"] / len(restores) if p and restores else None
