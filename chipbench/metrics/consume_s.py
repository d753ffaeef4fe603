"""Planning: phase_stats consume_copy + checksum wall per restore of the window:
what a read that did not land in place costs on the host after it arrived."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    found = [p for p in (phase(run, "consume_copy"), phase(run, "checksum")) if p]
    return sum(p["wall"] for p in found) / len(restores) if found and restores else None
