"""H2D: the wall of the dispatcher's device_put calls a leaf: the h2d_dispatch phase's wall over
the leaves the h2d_dispatch_route counter says those calls sent, in milliseconds.  None where the
library counts no leaves (a library from before the field) or none was sent."""
from chipbench.metrics._common import phase


def read(run):
    wall, route = phase(run, "h2d_dispatch"), phase(run, "h2d_dispatch_route")
    leaves = route.get("leaves") if route else None
    return 1000.0 * wall["wall"] / leaves if wall and leaves else None
