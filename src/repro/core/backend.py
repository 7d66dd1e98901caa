"""Static backend dispatch for the kernelized hot-path primitives.

The engine's three paper-hot primitives — clock tracker updates (§4.3),
approx-MSC candidate scoring (§5), and the compaction data plane (§4.2)
— each exist twice: a reference ``jnp`` implementation and a Pallas
kernel under ``repro.kernels``.  This module is the single place that
decides which one runs.

Dispatch is STATIC: ``backend`` is a Python string resolved at trace
time (it rides on ``EngineConfig``, which keys every jit cache), so the
reference path traces exactly the code it traced before the dispatch
layer existed — no ``lax.cond`` over pool state (the PR 4 branchless
invariant; see tests/test_hlo_budget.py) and zero HLO drift.

``interpret`` selects the Pallas interpreter.  ``None`` (the default
everywhere) auto-resolves from the runtime platform: interpret on CPU,
compiled on GPU/TPU — so a TPU caller that just flips
``backend="pallas"`` gets real kernels, not a silent interpreter run.
Forcing ``interpret=True`` on an accelerator is an error.
"""
from __future__ import annotations

import jax

REFERENCE = "reference"
PALLAS = "pallas"
BACKENDS = (REFERENCE, PALLAS)


def check(backend: str) -> str:
    """Validate a backend name (raise early, not mid-trace)."""
    if backend not in BACKENDS:
        raise ValueError(
            f"unknown backend {backend!r}; expected one of {BACKENDS}")
    return backend


def resolve_interpret(interpret: bool | None,
                      platform: str | None = None) -> bool:
    """Resolve the ``interpret`` knob for a Pallas call.

    ``None`` -> interpret only when the runtime platform is CPU (the
    interpreter is the only way to run these kernels there; on GPU/TPU
    the compiled kernel is the point).  ``True`` on an accelerator
    raises: it would discard the hardware it runs on.
    """
    if platform is None:
        platform = jax.default_backend()
    if interpret is None:
        return platform == "cpu"
    if interpret and platform != "cpu":
        raise ValueError(
            f"interpret=True on platform {platform!r} would run the Pallas "
            "kernels in the interpreter, not on the accelerator (pass "
            "interpret=None to auto-resolve)")
    return bool(interpret)
