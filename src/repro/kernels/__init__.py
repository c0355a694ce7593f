"""Pallas kernels. Every ``*_call`` entry point takes ``interpret=None``,
which resolves when the call is traced through :func:`pallas_interpret`."""
from __future__ import annotations

import jax


def pallas_interpret() -> bool:
    """Interpret mode for a Pallas call, chosen from the backend it is traced
    for: the CPU interprets, the TPU compiles, and any other backend has no
    kernel path. ``jax.default_device`` steers it like any other placement."""
    dev = jax.config.jax_default_device
    platform = getattr(dev, "platform", dev) or jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(f"Pallas kernels run on the TPU or interpreted on the CPU, "
                       f"not on backend {platform!r}")
