"""H2D: phase_stats h2d_drain wall per restore of the window: the tail no read hides."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    p = phase(run, "h2d_drain")
    return p["wall"] / len(restores) if p and restores else None
