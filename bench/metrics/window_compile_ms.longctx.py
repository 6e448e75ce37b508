"""JAX runtime under the engine: total time of the window's JAX
compiles (`jax.compile` spans, persistent-cache reads included), from
the program's spans (`repro.serving.spans`). 0 when the window holds
the engine's spans and no compile."""


def read(run):
    try:
        from repro.serving.spans import snapshot
    except ImportError:         # a program without the span recorder
        return None
    return from_snapshot(snapshot(), run)


def from_snapshot(snap, run):
    from repro.serving.spans import window
    w = window(snap, run["window"]["t0"], run["window"]["t_end"])
    if w is None or not any(s.name.startswith("engine.") for s in w):
        return None
    return 1e3 * sum(s.end - s.start for s in w if s.name == "jax.compile")
