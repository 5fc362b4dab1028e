"""Kernel execution-mode selection shared by all Pallas kernel wrappers.

``interpret=None`` everywhere means "auto": stage the kernel for the
platform's Pallas compiler wherever one exists (Mosaic on TPU, Triton on
GPU), and fall back to the Pallas interpreter only where none does (CPU CI,
unit tests).

A compiler existing does not mean a given kernel lowers. On TPU, Mosaic
refuses both AWAC sweep kernels (``awac_sweep`` and the persistent loop);
``core.single.NO_TPU_LOWERING`` records the refusals, and the solve path
routes around them instead of staging them here.

Every resolution returns/records an :class:`ExecutionMode` carrying the
explicit ``interpret`` flag, and the kernel wrappers thread it into bench
rows and ``MatchResult.execution`` so an interpreter timing can never
masquerade as a compiled-kernel timing.
"""
from __future__ import annotations

import dataclasses

import jax

#: Platforms that have a Pallas compiler: Mosaic on TPU, Triton on CUDA/ROCm
#: ("gpu" is the platform name older jax reports for both).
COMPILED_PLATFORMS = frozenset({"tpu", "gpu", "cuda", "rocm"})


@dataclasses.dataclass(frozen=True)
class ExecutionMode:
    """How a Pallas kernel actually executes.

    ``interpret``: True = Pallas interpreter (emulation; correctness-grade
    only — never a kernel timing). ``platform``: the jax default backend the
    resolution was made for. ``forced``: True when the caller pinned
    ``interpret`` explicitly rather than letting auto-detection decide.
    """

    interpret: bool
    platform: str
    forced: bool = False

    @property
    def ran_interpreted(self) -> bool:
        return self.interpret

    def describe(self) -> str:
        """Bench-row annotation, e.g. ``interpret=True``."""
        return f"interpret={self.interpret}"


#: Last mode any kernel wrapper resolved (trace-time side effect; host-side
#: diagnostics only — never read inside a traced computation).
_LAST_MODE: ExecutionMode | None = None


def resolve_execution(interpret: bool | None) -> ExecutionMode:
    """Resolve ``interpret`` to an explicit :class:`ExecutionMode`.

    Explicit True/False wins; ``None`` auto-detects: compiled wherever the
    platform has a Pallas lowering (see ``COMPILED_PLATFORMS``), interpreter
    elsewhere. Records the resolution for :func:`last_execution`.
    """
    global _LAST_MODE
    platform = jax.default_backend()
    if interpret is None:
        mode = ExecutionMode(interpret=platform not in COMPILED_PLATFORMS,
                             platform=platform)
    else:
        mode = ExecutionMode(interpret=bool(interpret), platform=platform,
                             forced=True)
    _LAST_MODE = mode
    return mode


def resolve_interpret(interpret: bool | None) -> bool:
    """Back-compat boolean view of :func:`resolve_execution` — every kernel
    wrapper funnels through here, so the resolved mode is always recorded."""
    return resolve_execution(interpret).interpret


def last_execution() -> ExecutionMode | None:
    """The most recently resolved mode (None before any kernel wrapper ran).

    Trace-time accurate: wrappers resolve the mode while tracing, so after a
    kernel call this reflects the mode that kernel was staged with.
    """
    return _LAST_MODE
