"""Engine: median over the window's admissions of the host's part of
one, `engine.admit` less its `engine.first_token` (the wait for the
prefill's token), from the program's spans (`repro.serving.spans`)."""
import statistics


def read(run):
    try:
        from repro.serving.spans import snapshot
    except ImportError:         # a program without the span recorder
        return None
    return from_snapshot(snapshot(), run)


def from_snapshot(snap, run):
    from repro.serving.spans import window
    w = window(snap, run["window"]["t0"], run["window"]["t_end"])
    if w is None:
        return None
    first = {s.parent: s.end - s.start for s in w
             if s.name == "engine.first_token"}
    host = [s.end - s.start - first[s.id] for s in w
            if s.name == "engine.admit" and s.id in first]
    return 1e3 * statistics.median(host) if host else None
