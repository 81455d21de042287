"""Where a Pallas kernel runs, and where compiled programs are kept.

Every kernel entry point takes ``interpret: bool | None``. ``None`` (the
default) lets the platform decide: the Pallas interpreter on ``cpu`` (the
only way a kernel executes there), the compiled Mosaic kernel everywhere
else. An explicit ``interpret=True`` on an accelerator raises instead of
silently running the interpreter there. An explicit ``interpret=False`` on
``cpu`` is allowed for the kernel entry points, because lowering for a
described (not attached) TPU topology is how the compile tests check the
kernels; code that *executes* kernels asks with ``executing=True``, which
refuses it (``core.backends.PallasBackend``).
"""
from __future__ import annotations

import os

import jax


def resolve_interpret(interpret: bool | None, *, executing: bool = False) -> bool:
    """The interpret flag a kernel call should use on this platform;
    ``executing=True`` also refuses compiled kernels on ``cpu``."""
    platform = jax.default_backend()
    if interpret is None:
        return platform == "cpu"
    if interpret and platform != "cpu":
        raise ValueError(
            f"Pallas interpret mode requested on platform {platform!r}; "
            "kernels are compiled there (pass interpret=None or False)"
        )
    if not interpret and executing and platform == "cpu":
        raise ValueError(
            "compiled Pallas kernels cannot execute on platform 'cpu' "
            "(pass interpret=None or True)"
        )
    return bool(interpret)


def enable_compile_cache(default_dir: str | os.PathLike) -> str:
    """Turn on JAX's persistent compilation cache; call before the first
    compile. Returns the directory in use.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and no
    directory is set here. Otherwise the cache goes to ``default_dir``,
    which must be a fixed path: the directory is where a later run looks,
    so a temp-, pid- or time-derived path would never hit. Every compile
    is cached, however short, so a warm run reuses the small kernels too.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not env:
        jax.config.update("jax_compilation_cache_dir", os.fspath(default_dir))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return env or os.fspath(default_dir)
