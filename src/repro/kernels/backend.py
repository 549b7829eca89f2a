"""Where a Pallas kernel runs: compiled on the TPU, interpreted on the CPU.

Every kernel entry point takes ``interpret=None`` and resolves it here,
so a caller on the chip never has to remember to turn the interpreter
off, and a CPU test never has to turn it on.
"""
from __future__ import annotations

import jax


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret`` as given, or derived from JAX's default backend when
    None: compiled Mosaic on ``tpu``, the Pallas interpreter on ``cpu``.
    Any other backend is an error — these kernels target the TPU only."""
    if interpret is not None:
        return bool(interpret)
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"Pallas kernels here run compiled on 'tpu' or interpreted on "
        f"'cpu'; the default backend is {backend!r}"
    )
