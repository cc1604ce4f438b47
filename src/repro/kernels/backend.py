"""Kernel backend detection — the dispatch axis for ``kernels/ops.py``.

Every accelerated op in this repo has up to three lowerings:

  * ``tpu`` — the Pallas MXU/VPU kernels (``dft.py``, ``autocorr.py``, ...),
    compiled on TPU, interpret-executed elsewhere for validation;
  * ``gpu`` — the Pallas Triton lowerings (``gpu.py``): plain-Pallas kernel
    bodies with no TPU-specific memory spaces or scratch, compiled via the
    Triton backend on GPU, interpret-executed elsewhere;
  * ``xla`` — pure-jnp fallbacks (``ref.py``) that run on any backend.

``kernel_backend()`` names the lowering the dispatch table should pick for
the running process; ``force_backend`` overrides it (tests use this to
exercise the gpu/xla rows of the table on a CPU host). ``resolve_interpret``
implements the auto-detection contract for the ``interpret=None`` kernel
default: a kernel compiles only when the *physical* platform matches its
target — the override never makes Pallas try to compile a Triton kernel on
a CPU host, it only routes dispatch.
"""
from __future__ import annotations

import contextlib
import os
from typing import Iterator, Optional

import jax

#: physical jax platforms each kernel target compiles on
_PLATFORMS = {"tpu": ("tpu",), "gpu": ("gpu", "cuda", "rocm")}

_OVERRIDE: Optional[str] = None


def kernel_backend() -> str:
    """The dispatch-table row for this process: 'tpu', 'gpu' or 'xla'."""
    if _OVERRIDE is not None:
        return _OVERRIDE
    b = jax.default_backend()
    if b in _PLATFORMS["tpu"]:
        return "tpu"
    if b in _PLATFORMS["gpu"]:
        return "gpu"
    return "xla"


def on_tpu() -> bool:
    return kernel_backend() == "tpu"


def on_gpu() -> bool:
    return kernel_backend() == "gpu"


def has_accelerator() -> bool:
    """True when a compiled kernel lowering (TPU or GPU) is the hot path.
    The pure-XLA row of the dispatch table serves every other backend."""
    return kernel_backend() in ("tpu", "gpu")


@contextlib.contextmanager
def force_backend(name: Optional[str]) -> Iterator[None]:
    """Force ``kernel_backend()`` for the dynamic extent (tests: exercise a
    foreign dispatch row; kernels then run in interpret mode — see
    ``resolve_interpret``). ``None`` restores auto-detection."""
    global _OVERRIDE
    if name is not None and name not in ("tpu", "gpu", "xla"):
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


def resolve_interpret(target: str, interpret: Optional[bool]) -> bool:
    """Auto-detect the ``interpret`` flag for a kernel aimed at ``target``:
    compiled when the running (physical) platform is the target, interpret
    mode everywhere else. An explicit True/False always wins."""
    if interpret is not None:
        return interpret
    return jax.default_backend() not in _PLATFORMS[target]
