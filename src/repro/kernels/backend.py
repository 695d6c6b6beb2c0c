"""The one interpret-or-compile decision for every Pallas kernel.

No kernel takes an ``interpret`` argument: each ``pallas_call`` asks
``interpret()`` at trace time, and the answer follows the backend the
process runs on.  The CPU backend (the test path) runs kernel bodies
through the Pallas interpreter; a TPU compiles them; any other backend
is refused, so no accelerator process can fall back to the interpreter.
"""

from __future__ import annotations

import jax


def interpret() -> bool:
    """True on the CPU backend, False on a TPU; raises elsewhere."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas kernels run compiled on a TPU or interpreted on the "
        f"CPU; the {platform!r} backend is neither")


def mode() -> str:
    """``"compiled"`` or ``"interpreted"``: the decision, for reports."""
    return "interpreted" if interpret() else "compiled"


__all__ = ["interpret", "mode"]
