"""Control plane: from the kill (`testbed.kill`) to the end of the first
route change (`router.set_route`) that sends one of the killed
worker's apps to another worker, from the program's spans
(`repro.serving.spans`): detection, the controller and the route."""


def read(run):
    try:
        from repro.serving.spans import snapshot
    except ImportError:         # a program without the span recorder
        return None
    return from_snapshot(snapshot(), run)


def from_snapshot(snap, run):
    from repro.serving.spans import window
    w = window(snap, run["window"]["t0"], run["window"]["t_end"])
    kill = next((s for s in w or () if s.name == "testbed.kill"), None)
    if kill is None:
        return None
    route = next((s for s in w if s.name == "router.set_route"
                  and s.start >= kill.start
                  and s.attrs["app"] in kill.attrs["apps"]
                  and s.attrs["server"] not in kill.attrs["servers"]),
                 None)
    return None if route is None else 1e3 * (route.end - kill.start)
