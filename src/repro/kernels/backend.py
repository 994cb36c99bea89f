"""Where the Pallas kernels run: compiled on an accelerator, interpreted on
the CPU.

Every kernel entry point takes ``interpret: bool | None = None`` and
resolves it here, so the choice is made in one place. ``None`` means "by
platform": the Pallas interpreter only when JAX's default backend is the
CPU (the test suite's backend), and a native Mosaic compile everywhere
else. Off the CPU a kernel that fails to compile raises; nothing falls
back to the interpreter or to the jnp reference. A caller that needs the
other mode says so explicitly — e.g. a test compiling for a described TPU
topology from a CPU host passes ``interpret=False``."""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    if interpret is None:
        return jax.default_backend() == "cpu"
    return interpret
