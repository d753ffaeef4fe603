"""H2D: how long a range of the restore's host arena is out, from its grant to a read to its give
by the lander: the arena_turn counter's turn_bs over its bytes, the byte-weighted mean seconds of a
turn over the restores of the window.  The arena's size is fixed by a rule, so a restore streams at
bytes lent over this.  None where the library has no such counter or no range completed a turn."""
from chipbench.metrics._common import phase


def read(run):
    p = phase(run, "arena_turn")
    return p["turn_bs"] / p["bytes"] if p and p.get("bytes") and "turn_bs" in p else None
