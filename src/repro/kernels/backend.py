"""Kernel backend detection — the dispatch axis for ``kernels/ops.py``.

Every accelerated op in this repo has two lowerings:

  * ``tpu`` — the Pallas MXU/VPU kernels (``dft.py``, ``autocorr.py``, ...),
    compiled on TPU, interpret-executed elsewhere for validation;
  * ``xla`` — pure-jnp fallbacks (``ref.py``) that run on any backend.

``kernel_backend()`` names the lowering the dispatch table should pick for
the running process; ``force_backend`` overrides it (a test hook: tests use
it to run the Pallas row, in interpret mode, on a CPU host).
``resolve_interpret`` implements the auto-detection contract for the
``interpret=None`` kernel default: a kernel compiles only on a physical
TPU — the override never makes Pallas compile for the TPU on a CPU host,
it only routes dispatch.
"""
from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional

import jax

_OVERRIDE: Optional[str] = None


def kernel_backend() -> str:
    """The dispatch-table row for this process: 'tpu' or 'xla'."""
    if _OVERRIDE is not None:
        return _OVERRIDE
    return "tpu" if jax.default_backend() == "tpu" else "xla"


def on_tpu() -> bool:
    return kernel_backend() == "tpu"


@contextlib.contextmanager
def force_backend(name: Optional[str]) -> Iterator[None]:
    """Force ``kernel_backend()`` for the dynamic extent (tests: exercise a
    foreign dispatch row; kernels then run in interpret mode — see
    ``resolve_interpret``). ``None`` restores auto-detection."""
    global _OVERRIDE
    if name is not None and name not in ("tpu", "xla"):
        raise ValueError(f"unknown kernel backend {name!r}")
    prev, _OVERRIDE = _OVERRIDE, name
    try:
        yield
    finally:
        _OVERRIDE = prev


def use_compile_cache(default_dir) -> str:
    """Keep JAX's persistent compilation cache where
    ``JAX_COMPILATION_CACHE_DIR`` says (JAX reads that variable itself),
    else in ``default_dir``. Callers pass a fixed path, so the cache's key
    stays the same from one run to the next. Returns the directory used."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(default_dir))
    return str(default_dir)


def resolve_interpret(interpret: Optional[bool]) -> bool:
    """Auto-detect the ``interpret`` flag of a Pallas kernel: compiled when
    the running (physical) platform is the TPU, interpret mode everywhere
    else. An explicit True/False always wins."""
    if interpret is not None:
        return interpret
    return jax.default_backend() != "tpu"
