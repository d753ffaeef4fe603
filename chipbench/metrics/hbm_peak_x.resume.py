"""Device: peak_bytes_in_use of the fullest device over its share of the state (resume cells)."""
from chipbench.metrics._common import window_restores


def read(run):
    c = run["counters"]
    if not window_restores(run) or not c.get("hbm_peak_bytes"):
        return None
    return c["hbm_peak_bytes"] / c["state_bytes_fullest_device"]
