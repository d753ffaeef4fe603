"""Storage: fs_read bytes over its wall-union, the sequential route's rate as read_gbps is the
native pool's.  None where no read went that way."""
from chipbench.metrics._common import phase


def read(run):
    p = phase(run, "fs_read")
    return p["bytes"] / p["wall"] / 1e9 if p and p.get("wall", 0) > 0 and p["bytes"] else None
