"""Which Pallas kernels run compiled and which run in the interpreter.

Every kernel wrapper takes a ``backend`` argument.  Its Pallas values are

  * ``"pallas"``     — platform-adaptive: compiled on a TPU, interpreted
                       elsewhere (CPU tests and development machines);
  * ``"pallas_tpu"`` — compiled unconditionally (fails off-TPU).
"""
from __future__ import annotations

import re

import jax


def interpret(backend: str) -> bool:
    """True where ``backend``'s Pallas kernel must run in interpret mode."""
    return backend == "pallas" and jax.default_backend() != "tpu"


def tpu_kernels(hlo_text: str) -> set:
    """Names of the compiled Pallas kernels that an HLO module calls: the
    instructions whose target is ``tpu_custom_call`` (a kernel run in
    interpret mode leaves none)."""
    return {m.group(1) for m in re.finditer(
        r"%([A-Za-z_][\w-]*?)(?:\.\d+)* = [^\n]*"
        r"custom_call_target=\"tpu_custom_call\"", hlo_text)}
