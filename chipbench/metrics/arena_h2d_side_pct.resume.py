"""H2D: of the byte-seconds the restore's host arena lent (the arena_turn counter's turn_bs), the
share a range spends with the H2D side, from its leaf's submit to the batcher to its give by the
lander: the stages gather, dispatch and land, in percent.  None where the library has no such
counter or no range completed a turn."""
from chipbench.metrics._common import phase

H2D_SIDE = ("gather_bs", "dispatch_bs", "land_bs")


def read(run):
    p = phase(run, "arena_turn")
    if not (p and p.get("turn_bs")):
        return None
    return 100.0 * sum(p[key] for key in H2D_SIDE) / p["turn_bs"]
