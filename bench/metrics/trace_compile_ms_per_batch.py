"""Milliseconds per batch the window spent tracing jaxprs, lowering them
to MLIR and compiling or fetching executables (the backend-compile event
covers the persistent-cache read), from ``jax.monitoring`` durations."""

EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
          "/jax/core/compile/jaxpr_to_mlir_module_duration",
          "/jax/core/compile/backend_compile_duration")


def read(obs):
    if not obs["batches"]:
        return None
    secs = sum(obs["events"].get(e, [0, 0.0])[1] for e in EVENTS)
    return 1e3 * secs / obs["batches"]
