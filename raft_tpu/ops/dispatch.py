"""Kernel-tier dispatch: when to route a primitive to its Pallas kernel.

The reference makes the equivalent choice at CMake/template-instantiation
time (precompiled specializations vs header-only paths,
``cpp/CMakeLists.txt:236-406``); here it is a runtime decision per call:

* on a TPU backend the Pallas kernels compile natively (Mosaic);
* elsewhere (the CPU test mesh) they can still run under the Pallas
  interpreter for correctness tests, but are off by default because the
  XLA formulation is faster on CPU.

``RAFT_TPU_PALLAS`` overrides: ``never`` | ``auto`` (default) |
``always`` (use Pallas even off-TPU, interpreted off-TPU — what the unit
tests set).
"""

from __future__ import annotations

import os
from typing import Optional

import jax

from raft_tpu import obs


_MODES = ("auto", "0", "never", "off", "1", "always", "on")


def _mode() -> str:
    mode = os.environ.get("RAFT_TPU_PALLAS", "auto").lower()
    if mode not in _MODES:
        raise ValueError(
            f"RAFT_TPU_PALLAS={mode!r}: want auto|never|always")
    return mode


def pallas_available() -> bool:
    """True when the Pallas TPU lowering path exists for this process."""
    try:
        from jax.experimental import pallas  # noqa: F401
        return True
    except ImportError:  # pragma: no cover - pallas ships with jax
        return False


def pallas_enabled(backend: Optional[str] = None) -> bool:
    """Should a primitive route to its Pallas kernel? Every call counts
    the decision into ``raft.dispatch.route{path=pallas|xla}`` — the
    telemetry that says which kernel tier actually served traffic
    (bench rows embed the diff, so they name their code path)."""
    mode = _mode()
    if mode in ("0", "never", "off"):
        use = False
    elif mode in ("1", "always", "on"):
        use = pallas_available()
    else:
        backend = backend or jax.default_backend()
        use = backend == "tpu" and pallas_available()
    obs.counter("raft.dispatch.route",
                path="pallas" if use else "xla").inc()
    return use


def pallas_interpret(backend: Optional[str] = None) -> bool:
    """Run kernels under the Pallas interpreter (non-TPU backends)."""
    backend = backend or jax.default_backend()
    interp = backend != "tpu"
    if interp:
        # interpret-mode fallback: correct but orders of magnitude
        # slower than a compiled kernel — worth a counter of its own
        obs.counter("raft.dispatch.interpret_fallback").inc()
    return interp
