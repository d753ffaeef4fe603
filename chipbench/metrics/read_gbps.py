"""Storage: native_read (or fs_read) bytes over its wall-union."""
from chipbench.metrics._common import phase


def read(run):
    p = phase(run, "native_read") or phase(run, "fs_read")
    return p["bytes"] / p["wall"] / 1e9 if p and p["wall"] > 0 and p["bytes"] else None
