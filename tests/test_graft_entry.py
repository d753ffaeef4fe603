"""The driver surface's multichip dryrun must hold across mesh shapes —
degenerate 1-device, prime-ish 6-device factorings — not just the happy
8-device case, with the chunked-array and host-offload paths active
(round-3 verdict item).  The driver itself runs n=8."""

import sys

import pytest


@pytest.mark.parametrize("n", [1, 6])
def test_dryrun_multichip_shapes(n, monkeypatch, tmp_path):
    # The entry point places the compile cache; with the variable set it sets
    # nothing, so this process (the whole test session) keeps none.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    sys.path.insert(0, "/root/repo")
    try:
        import __graft_entry__ as g

        g.dryrun_multichip(n)
    finally:
        sys.path.remove("/root/repo")
