"""Process-level runtime set-up shared by the entry points.

Two things every entry point that compiles for the chip does first
(`chip_smoke.py`, `bench.py`, `scripts/tpu_smoke.py`, `cli`):

  * place JAX's persistent compilation cache (:func:`configure_compile_cache`)
    — the cache key includes the directory, so it must never move;
  * for the on-chip entry points, refuse to run without a TPU
    (:func:`require_tpu`) — a measurement path that finds no chip fails,
    it does not fall back to the CPU backend or the Pallas interpreter.
"""

from __future__ import annotations

import os

import jax

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the in-checkout cache directory used when ``JAX_COMPILATION_CACHE_DIR``
#: is unset (listed in ``.gitignore``)
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


class NoAcceleratorError(RuntimeError):
    """An on-chip entry point was started on a backend that is not a TPU."""


def configure_compile_cache() -> str:
    """Place the persistent compilation cache; returns the directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads it itself and this
    sets nothing.  Otherwise the cache lives at the fixed
    :data:`DEFAULT_COMPILE_CACHE_DIR` — never a temp-, pid- or
    time-derived path, which would never hit.  Call before the first
    compile."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR


def device_summary() -> dict:
    """``{"platform", "kind", "count"}`` of the default backend, as JAX
    reports it — what every benchmark result carries."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def describe_run(device: dict, cache_dir: str) -> str:
    """The one line every on-chip entry point prints first: the device
    as JAX reports it, where compiles are cached, and which tuning
    tables decide the tiles."""
    from attention_tpu.tuning.lookup import tables_in_use

    return (f"platform={device['platform']} device_kind={device['kind']} "
            f"count={device['count']}; compile cache: {cache_dir}; "
            f"tuning tables: {tables_in_use()}")


def require_tpu() -> dict:
    """:func:`device_summary`, or `NoAcceleratorError` naming the
    platform found when the default backend is not a TPU."""
    dev = device_summary()
    if dev["platform"] != "tpu":
        raise NoAcceleratorError(
            f"this entry point measures the TPU and found "
            f"platform={dev['platform']} kind={dev['kind']!r} "
            f"count={dev['count']}; it does not fall back to the CPU "
            "backend or the Pallas interpreter"
        )
    return dev
