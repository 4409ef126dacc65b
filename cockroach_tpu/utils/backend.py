"""JAX backend selection and the persistent compile cache.

The chip is attached to this machine directly and belongs to one process at
a time. Nothing here probes for it or hides it: production entry points take
whatever ``jax.devices()`` reports. ``force_cpu_backend`` is for the tier-1
tests and dry-run scripts, which run on a virtual CPU mesh.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> tuple[str, bool]:
    """(directory, from_env) of the persistent compilation cache: where
    ``JAX_COMPILATION_CACHE_DIR`` points when it is set (JAX reads that
    variable itself), else the fixed ``<checkout>/.jax_cache`` — the path
    is part of the cache key, so it is never built from a temporary name."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env, True
    return os.path.join(_CHECKOUT, ".jax_cache"), False


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache at ``compile_cache_dir()``
    and return the directory. The one place the program decides this:
    Session, Node, cli.py and chip_smoke.py all call it.
    With the variable set nothing is written to ``jax_compilation_cache_dir``
    in code — JAX already holds the environment's value."""
    import jax

    cache_dir, from_env = compile_cache_dir()
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # every entry point comes through here once, so this is also where the
    # tracer starts counting backend compiles by owner
    from . import tracing

    tracing.install_compile_listener()
    return cache_dir


_F64_BITCAST_OK: bool | None = None


def float_bitcast_ok() -> bool:
    """One-time check: does this backend compile f64<->u32 bitcasts
    CORRECTLY (negative doubles, denormals, signed zero round-trip bit for
    bit)? Float-keyed joins/hashes depend on it and must fail LOUDLY rather
    than silently match wrong rows. A compile error propagates: it is not
    evidence of a miscompile. chip_smoke.py prints the verdict.

    Observed on an attached v5e (jax 0.9.0, libtpu 0.0.34, PR 22): the
    check does not compile — "UNIMPLEMENTED: While rewriting computation
    to not contain X64 element types, XLA encountered an HLO for which
    this rewriting is not implemented: bitcast-convert". The CPU passes."""
    global _F64_BITCAST_OK
    if _F64_BITCAST_OK is not None:
        return _F64_BITCAST_OK
    import jax
    import jax.numpy as jnp
    import numpy as np

    vals = np.array([-1.5, -0.0, 2.5e-308, -1e300, 3.25], dtype=np.float64)
    want = vals.view(np.uint64)

    def roundtrip(x):
        parts = jax.lax.bitcast_convert_type(x, jnp.uint32)  # [..., 2]
        u = (parts[..., 1].astype(jnp.uint64) << jnp.uint64(32)
             ) | parts[..., 0].astype(jnp.uint64)
        back = jax.lax.bitcast_convert_type(parts, jnp.float64)
        return u, back

    u, back = jax.jit(roundtrip)(jnp.asarray(vals))  # crlint: allow-raw-jit(one-shot backend check, not a query kernel)
    _F64_BITCAST_OK = bool(
        np.array_equal(np.asarray(u), want)
        and np.array_equal(np.asarray(back).view(np.uint64), want))
    return _F64_BITCAST_OK


def require_float_bitcast(what: str) -> None:
    """Raise a clear error when a float-keyed kernel would miscompile, or
    cannot be compiled at all — and say which of the two it is."""
    import jax

    try:
        ok = float_bitcast_ok()
    except jax.errors.JaxRuntimeError as e:
        raise NotImplementedError(
            f"{what}: this backend's compiler refuses f64 bitcasts "
            f"({str(e).splitlines()[0][:160]}); float join/group keys are "
            "disabled on it. Cast the key to DECIMAL or INT instead."
        ) from e
    if not ok:
        raise NotImplementedError(
            f"{what}: this backend miscompiles f64 bitcasts (the round "
            "trip changed bits); float join/group keys are disabled on it. "
            "Cast the key to DECIMAL or INT instead."
        )


def force_cpu_backend(n_devices: int | None = None) -> None:
    """Hold jax to the CPU backend, with an optional virtual device count.
    Call before the first device use: backends initialize lazily, and an
    initialized one is not evicted."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    if n_devices is not None:
        import re

        # replace any pre-existing count: the requested mesh size must win
        flags = re.sub(
            r"--xla_force_host_platform_device_count=\d+", "",
            os.environ.get("XLA_FLAGS", ""),
        )
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()

    import jax

    # jax may already be imported, freezing jax_platforms at the earlier env
    # value — set the live config too
    jax.config.update("jax_platforms", "cpu")
