"""Engine on the backup: the first admission after the kill on another
worker than the killed one, `engine.admit` from its start to the first
token, from the program's spans (`repro.serving.spans`)."""


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
    admit = next((s for s in w if s.name == "engine.admit"
                  and s.start >= kill.start
                  and s.attrs.get("server") not in kill.attrs["servers"]),
                 None)
    return None if admit is None else 1e3 * (admit.end - admit.start)
