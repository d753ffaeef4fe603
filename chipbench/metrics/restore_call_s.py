"""Orchestration: host clock around restore_latest, mean over the window's restores."""
from chipbench.metrics._common import window_restores


def read(run):
    calls = [r["restore_call_s"] for r in window_restores(run)]
    return sum(calls) / len(calls) if calls else None
