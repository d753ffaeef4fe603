"""Orchestration: seconds of each Snapshot.restore call that no phase's interval
covers (the restore_unattributed counter), per restore of the window."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    p = phase(run, "restore_unattributed")
    return p["s"] / len(restores) if p and restores else None
