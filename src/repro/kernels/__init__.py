"""Pallas kernels for the DMF hot paths (`dmf_update`, `dp_noise`,
`topk_scores`, `serve_topk`, `gossip_mix`), their jit'd wrappers (`ops`)
and pure-jnp oracles (`ref`)."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """The one place the Pallas execution mode is chosen. ``None`` (every
    default) compiles the kernel with Mosaic on a TPU backend and runs the
    Pallas interpreter on any other backend; an explicit bool wins (the
    compile tests pass ``False`` to lower for a described TPU from a CPU
    process)."""
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)
