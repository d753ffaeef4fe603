"""Planning: bytes the restores of the window planned to read into place by the sequential route
(the read_route counter's sequential: leaves of a megabyte and more under the striped minimum,
read and hashed in one pass by fs_read, never by the native pool) over the state's bytes a restore,
in percent.  None where the library has no such counter."""
from chipbench.metrics._common import phase, window_restores


def read(run):
    restores = window_restores(run)
    p = phase(run, "read_route")
    state = run["counters"].get("state_bytes")
    if not (p and restores and state) or "sequential" not in p:
        return None
    return 100.0 * p["sequential"] / (state * len(restores))
