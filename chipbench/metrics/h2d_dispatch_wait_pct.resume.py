"""H2D: of the wall of the dispatcher's device_put calls (the h2d_dispatch phase), the share the
calling thread spent off the CPU: the wall less the h2d_dispatch_route counter's cpu_s (the thread's
CPU seconds inside the calls), over the wall, in percent.  None where the library reads no such
clock (a library from before the field) or no leaf was sent."""
from chipbench.metrics._common import phase


def read(run):
    wall, route = phase(run, "h2d_dispatch"), phase(run, "h2d_dispatch_route")
    if not (wall and wall["wall"] and route and route.get("leaves") and "cpu_s" in route):
        return None
    return 100.0 * (wall["wall"] - route["cpu_s"]) / wall["wall"]
