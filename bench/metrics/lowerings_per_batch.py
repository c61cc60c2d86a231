"""Jaxprs lowered to MLIR modules inside the window, per batch
(``jax.monitoring`` event ``/jax/core/compile/jaxpr_to_mlir_module_duration``)."""

EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def read(obs):
    if not obs["batches"]:
        return None
    return obs["events"].get(EVENT, [0, 0.0])[0] / obs["batches"]
