"""End to end: the window's length over the restores completed in it, each
from dropping the live state to the first finished train step."""
from chipbench.metrics._common import window_restores


def read(run):
    done = window_restores(run)
    window_s = run["account"].window_s
    return window_s / len(done) if done and window_s > 0 else None
