"""Kernel implementation selector.

impl = 'pallas'            the Pallas TPU kernels — the default on a TPU
                           backend, and an error to select on any other
impl = 'xla'               chunked pure-jnp path — the default on every
                           other backend (the CPU tests, the dry-run)
impl = 'pallas_interpret'  Pallas kernel body executed in Python on CPU —
                           used by the correctness test sweeps

Until ``set_impl``/``use_impl`` names one, ``get_impl`` answers the
backend's default, so a program traced on the chip runs its kernels and
the same program traced on the CPU runs the jnp paths.  The choice is read
at trace time: jitted programs keep the impl they were traced under.
"""
from __future__ import annotations

import contextlib
import threading

import jax

_VALID = ("xla", "pallas", "pallas_interpret")


class _State(threading.local):
    def __init__(self):
        self.impl = None  # None: the backend's default


_STATE = _State()


def default_impl() -> str:
    """'pallas' on a TPU backend, 'xla' on any other."""
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def get_impl() -> str:
    return _STATE.impl or default_impl()


def set_impl(impl: str) -> None:
    if impl not in _VALID:
        raise ValueError(
            f"unknown kernel impl {impl!r}: expected one of {_VALID}"
        )
    if impl == "pallas" and jax.default_backend() != "tpu":
        raise RuntimeError(
            f"kernel impl 'pallas' needs a TPU backend, found "
            f"{jax.default_backend()!r}: use 'pallas_interpret' to run the "
            "kernel bodies here, or 'xla'"
        )
    _STATE.impl = impl


@contextlib.contextmanager
def use_impl(impl: str):
    prev = _STATE.impl
    set_impl(impl)
    try:
        yield
    finally:
        _STATE.impl = prev


def with_jnp_vjp(kernel, jnp_path):
    """``kernel`` on the forward pass, ``jnp_path``'s vjp on the backward.

    A ``pallas_call`` has no transpose rule, so ``jax.grad`` cannot go
    through a kernel.  This keeps the kernel's forward and differentiates
    the jnp path instead, recomputing its forward from the saved inputs.
    Both take the same positional array arguments (None allowed); static
    options are bound beforehand."""
    fn = jax.custom_vjp(kernel)
    fn.defvjp(
        lambda *args: (kernel(*args), args),
        lambda args, g: jax.vjp(jnp_path, *args)[1](g),
    )
    return fn

