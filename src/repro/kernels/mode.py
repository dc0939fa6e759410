"""Where the Pallas kernels run: compiled on TPU, interpreted on CPU."""
from __future__ import annotations

import jax

__all__ = ["interpret_mode"]


def interpret_mode(devices=None) -> bool:
    """Whether Pallas kernels placed on ``devices`` run in interpret mode.

    The kernels are TPU kernels: Mosaic compiles them for TPU devices, and
    the CPU runs them in interpret mode, which is how the tests validate
    them.  ``devices=None`` means the default backend's devices.

    Raises:
      ValueError: for devices of any other platform, or a mix of platforms.
    """
    if devices is None:
        platforms = {jax.default_backend()}
    else:
        platforms = {d.platform for d in devices}
    if platforms == {"tpu"}:
        return False
    if platforms == {"cpu"}:
        return True
    raise ValueError(
        "the Pallas kernels run compiled on TPU or interpreted on CPU; "
        f"got devices of platform {sorted(platforms)}"
    )
