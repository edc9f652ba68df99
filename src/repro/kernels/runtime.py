"""Shared kernel-runtime knobs for every Pallas kernel family.

One switch decides whether Pallas kernels run compiled or interpreted:

  ``REPRO_PALLAS_INTERPRET`` — environment override, highest precedence.
    ``1``/``true``/``on``/``yes`` force interpreter mode everywhere;
    ``0``/``false``/``off``/``no`` force compiled kernels everywhere;
    ``auto`` (or unset) defers to the caller / backend autodetect.

  explicit ``interpret=`` argument — per-callsite override, used by unit
    tests that pin interpreter mode regardless of the host.

  autodetect — ``interpret=None`` resolves to "interpret off-TPU": the
    same backend name works on the CPU CI box (interpreted) and on real
    hardware (compiled) without touching any callsite.

All four kernel families (``extend_fused``, ``intersect``, ``segsum``,
``flash_attention``) resolve their ``interpret`` default through
:func:`resolve_interpret`, so CI and real hardware flip one switch
instead of auditing every callsite.

:data:`TPU_REFUSALS` records the mining kernels the TPU compiler
(Mosaic, jax/jaxlib 0.9.0 with libtpu 0.0.34) refuses to lower, with its
message; :func:`require_lowerable` turns a compiled-mode request for one
of them on a TPU into an error raised before anything is traced, so no
caller silently drops to interpret mode or to the XLA path.
``tests/test_chip_compile.py`` keeps the table honest by compiling each
kernel for a described v5e chip.
"""
from __future__ import annotations

# repro: host-module
# Config resolution only (env vars, backend autodetect) — runs before
# any kernel traces, never inside one.

import os

import jax

ENV_VAR = "REPRO_PALLAS_INTERPRET"

_TRUE = frozenset({"1", "true", "on", "yes"})
_FALSE = frozenset({"0", "false", "off", "no"})


def env_interpret() -> bool | None:
    """The ``REPRO_PALLAS_INTERPRET`` setting, or None when unset/auto."""
    raw = os.environ.get(ENV_VAR, "").strip().lower()
    if raw in _TRUE:
        return True
    if raw in _FALSE:
        return False
    if raw in ("", "auto"):
        return None
    raise ValueError(
        f"{ENV_VAR}={raw!r}: expected one of 1/0/true/false/on/off/yes/no"
        "/auto")


def resolve_interpret(interpret: bool | None = None) -> bool:
    """Resolve an ``interpret=`` argument to a concrete bool.

    Precedence: the ``REPRO_PALLAS_INTERPRET`` environment variable (the
    fleet-wide switch) > the explicit per-callsite argument > autodetect
    (interpret everywhere except on a real TPU backend).
    """
    env = env_interpret()
    if env is not None:
        return env
    if interpret is not None:
        return bool(interpret)
    return jax.default_backend() != "tpu"


# Kernel -> the TPU compiler's refusal.  The 1-D VMEM gathers
# (``jnp.take`` on a flat ref) are the common cause; the two-pass
# kernel's first pass already stops at its (1,)-element count block.
TPU_REFUSALS = {
    "fused_extend": "NotImplementedError: Only 2D gather is supported",
    "fused_extend_pruned":
        "NotImplementedError: Only 2D gather is supported",
    "fused_extend_pruned_mp":
        "ValueError: The Pallas TPU lowering currently requires that rank "
        "1 block shapes ... be a multiple of the tiling size (pass 1, "
        "i32[1] count block); pass 2: NotImplementedError: Only 2D gather "
        "is supported",
    "fused_extend_edge": "NotImplementedError: Only 2D gather is supported",
    "intersect_count": "NotImplementedError: Only 2D gather is supported",
}


class KernelNotLowerable(RuntimeError):
    """A compiled Pallas kernel was requested that the TPU cannot lower."""


def require_lowerable(kernels, what: str, interpret: bool) -> None:
    """Refuse ``what`` up front when it would launch a compiled kernel
    that the TPU compiler refuses (see :data:`TPU_REFUSALS`)."""
    if interpret or jax.default_backend() != "tpu":
        return
    refused = [f"{k}: {TPU_REFUSALS[k]}" for k in kernels
               if k in TPU_REFUSALS]
    if refused:
        raise KernelNotLowerable(
            f"{what} needs Pallas kernels the TPU compiler refuses in "
            f"compiled mode ({'; '.join(refused)}); use "
            "backend='reference' (pure XLA) on the TPU")
