"""H2D: phase_stats h2d_dispatch + h2d_land wall per restore of the window."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    found = [p for p in (phase(run, "h2d_dispatch"), phase(run, "h2d_land")) if p]
    return sum(p["wall"] for p in found) / len(restores) if found and restores else None
