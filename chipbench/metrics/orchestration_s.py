"""Orchestration: phase_stats restore_open + load_state wall per restore of the window."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    found = [p for p in (phase(run, "restore_open"), phase(run, "load_state")) if p]
    return sum(p["wall"] for p in found) / len(restores) if found and restores else None
