"""H2D: phase_stats arena_populate wall per restore of the window: the restore's host arena, every
page of it written once, in parallel on the native pool, before the first read is handed a range of
it (one interval a restore that made an arena).  None where the phase never fired: a library from
before the population, or a state that needs no arena."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    p = phase(run, "arena_populate")
    return p["wall"] / len(restores) if p and restores else None
