"""Backend compilations JAX itself reports in the window, whoever asked for
them: the storage plane's raw `jax.jit` kernels are seen by no counter of
the program, and a compile in the window is a fault of the warm-up."""

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


def begin(ctx):
    import jax.monitoring

    state = {"n": 0, "s": 0.0}

    def on_duration(event, seconds, **_kw):
        if event == COMPILE_EVENT:
            state["n"] += 1
            state["s"] += seconds

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    state["cb"] = on_duration
    return state


def read(ctx, state):
    import jax.monitoring

    try:
        jax.monitoring.unregister_event_duration_listener(state["cb"])
    except (AttributeError, ValueError):
        pass
    return float(state["n"])
