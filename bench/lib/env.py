"""Process environment that has to be set before JAX is imported."""

import os


def prepare() -> None:
    """Keep the host CPU beside the accelerator (the reference runs there),
    and pass closed-over arrays to compiled programs as arguments rather
    than as literals, so that a program's compile-cache key does not depend
    on the weights a seed makes."""
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
    os.environ.setdefault("JAX_USE_SIMPLIFIED_JAXPR_CONSTANTS", "1")
