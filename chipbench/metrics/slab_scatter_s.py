"""Planning: phase_stats slab_scatter wall per restore of the window: merged slab
reads being fanned out to their members (leaves under a megabyte), to the last member's consume."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    p = phase(run, "slab_scatter")
    return p["wall"] / len(restores) if p and restores else None
