"""Scheduler: wall per restore of the waits the read pipeline records, whichever it
did: read_starved (alive, no read in flight), budget_wait, io_slot_wait and
h2d_window_wait.  The last three run beside reads in flight."""
from chipbench.metrics._common import phase, window_restores

WAITS = ("read_starved", "budget_wait", "io_slot_wait", "h2d_window_wait")


def read(run):
    restores = window_restores(run)
    found = [p for p in (phase(run, name) for name in WAITS) if p]
    return sum(p["wall"] for p in found) / len(restores) if found and restores else None
