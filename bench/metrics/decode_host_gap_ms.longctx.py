"""Engine: median over consecutive decode steps of one request of the
host's part of a token, from the end of a step's `engine.sync` (its
tokens on the host) to the end of the next step's `engine.decode` (the
next step dispatched), from the program's spans (`repro.serving.spans`)."""
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
    synced, gaps = {}, []
    for s in sorted(w, key=lambda s: s.end):
        if s.name == "engine.sync":
            synced.update((rid, s.end) for rid in s.attrs["ids"])
        elif s.name == "engine.decode":
            gaps += [s.end - synced[rid] for rid in s.attrs["ids"]
                     if rid in synced]
    return 1e3 * statistics.median(gaps) if gaps else None
