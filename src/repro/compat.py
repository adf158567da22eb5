"""Platform helpers shared by the kernels, meshes and launchers.

The repo supports one jax version, the installed 0.9.0; nothing here
branches on the version.
"""

from __future__ import annotations

import os

import jax
from jax.sharding import AxisType

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """`jax.make_mesh` with every axis `Auto`.

    jax 0.9 makes every axis `Explicit` by default, and a gather on an
    operand sharded over an explicit axis raises `ShardingTypeError`. The
    solver and the sharded serving path leave sharding to the compiler, so
    every mesh of the repo is built here."""
    names = tuple(axis_names)
    return jax.make_mesh(tuple(axis_shapes), names,
                         axis_types=(AxisType.Auto,) * len(names),
                         devices=devices)


def default_pallas_interpret() -> bool:
    """Backend-appropriate default for pallas_call's `interpret=`: compiled
    Mosaic kernels on TPU, the (slow, portable) interpreter everywhere else.
    Callers that take `interpret: bool | None = None` resolve None through
    this so CPU CI and real TPU lanes share one code path."""
    return jax.default_backend() != "tpu"


def resolve_interpret(interpret) -> bool:
    """None -> backend default, anything else -> bool(it)."""
    return default_pallas_interpret() if interpret is None else bool(interpret)


def refuse_shared_accelerator(n_workers: int, what: str) -> None:
    """Raise before starting `n_workers` worker processes that would all
    need this process's accelerator.

    A chip belongs to one process at a time: once this process has touched
    it, a child that needs it fails or hangs. Worker subprocesses therefore
    run only where the backend is the CPU."""
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"{what} starts {n_workers} worker processes that would each "
            f"need a {backend} chip, but this process already holds the "
            f"{jax.local_device_count()} local chip(s) and a chip serves "
            "one process at a time. Run it with JAX_PLATFORMS=cpu, or start "
            "one worker per host.")


def enable_compile_cache() -> str:
    """Turn on jax's persistent compile cache and return its directory.

    Where `JAX_COMPILATION_CACHE_DIR` is set, jax already reads it and
    nothing else is set. Otherwise the cache sits at one fixed path inside
    the checkout, `<repo>/.jax_cache` (git-ignored): the path is part of
    the cache key, so a directory that moved between runs would never hit.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = os.path.join(REPO_ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
