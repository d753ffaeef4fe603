"""Scheduler: of the byte-seconds the restore's host arena lent (the arena_turn counter's turn_bs),
the share in the four stages in which a range is held and nobody works on it: grant (fitted, not yet
adopted by its leaf), slot (waiting for an io slot, range in hand), parked (read, behind the loader)
and gather (submitted, not yet taken by the dispatcher with window room), in percent.  None where
the library has no such counter or no range completed a turn."""
from chipbench.metrics._common import phase

WAITS = ("grant_bs", "slot_bs", "parked_bs", "gather_bs")


def read(run):
    p = phase(run, "arena_turn")
    if not (p and p.get("turn_bs")):
        return None
    return 100.0 * sum(p[key] for key in WAITS) / p["turn_bs"]
