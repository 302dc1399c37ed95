"""Hand-written Pallas kernels.

One kernel lives here: `hash_build`, the hash-join build over
dense-int keys — per-slot row index and key count accumulated in VMEM
slot tiles by a one-hot tile sweep (XLA's scatter alternative is serial
on TPU).  It runs in 32-bit lanes only; Mosaic lowers no 64-bit type,
which is why the engine's f64/int64 aggregates and int64 sort keys have
no kernel here and take the stock XLA lowerings.

The kernel engages by a stated rule, never by trying and catching:
the slot table fits `BUILD_MAX_SLOTS` and `enabled_for(...)` holds.
``DATAFUSION_TPU_PALLAS`` selects the mode:

- ``auto`` (default): engage when batches execute on a TPU.  The
  kernel accumulates into a revisited output tile, which relies on
  TPU's sequential grid iteration; a backend that runs grid steps in
  parallel would race.
- ``interpret``: run through the Pallas interpreter — slow but correct
  anywhere; this is how the CPU test suite proves kernel parity
  against the numpy oracle.
- ``0``: off everywhere (stock XLA scatter build).

A kernel the compiler refuses is a query error, not a fallback:
`chip_smoke.py` compiles it on the chip at its window-edge shape.
"""

from __future__ import annotations

import os

# Largest direct-address slot table the hash-build kernel fills; above
# it the stock-XLA scatter build keeps the job (the one-hot tile sweep
# is linear in the slot count).
BUILD_MAX_SLOTS = 8192


def _mode() -> str:
    return os.environ.get("DATAFUSION_TPU_PALLAS", "auto")


def interpret_mode() -> bool:
    return _mode() == "interpret"


def enabled_for(device) -> bool:
    """Should the Pallas kernel engage for an operator whose batches
    run on `device` (a jax Device, or None = the JAX default backend)?
    See the module docstring."""
    mode = _mode()
    if mode == "0":
        return False
    if mode == "interpret":
        return True
    if device is not None:
        return device.platform == "tpu"
    import jax

    return jax.default_backend() == "tpu"
