"""The XLA setting the serving engine's batch invariance needs on a TPU.

With excess precision allowed (XLA's default), the TPU compiler keeps the
bf16 intermediates of a fused elementwise chain in f32 and skips the
roundings the program writes — and which ones it skips depends on how
each program is fused.  The continuous engine computes a token either in
a mixed prefill+decode step or in the fused decode loop, two programs, so
a request's tokens would depend on which requests shared its batch
(observed on a v5e chip; the CPU backend rounds the same in both).  With
``--xla_allow_excess_precision=false`` every written rounding is kept
(the compiler inserts ``reduce-precision``), and both programs compute
the same bits.

Entry points call :func:`exact_rounding` from ``main``, before JAX
creates its backends; importing this module changes nothing.
"""

from __future__ import annotations

import os

FLAG = "--xla_allow_excess_precision=false"


def exact_rounding() -> str:
    """Append :data:`FLAG` to ``XLA_FLAGS`` — never replacing what is set
    there — and return the new value.  Takes effect only if JAX has not
    yet created a backend in this process."""
    flags = os.environ.get("XLA_FLAGS", "")
    if FLAG not in flags.split():
        flags = f"{flags} {FLAG}".strip()
        os.environ["XLA_FLAGS"] = flags
    return flags
