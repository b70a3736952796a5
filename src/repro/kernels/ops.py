"""Dispatch of the batched cluster event step: Pallas on TPU, jnp elsewhere.

:func:`event_step` is the entry every scan bucket runs through
(``repro.core.fastpath._runner_fns`` jits it once per bucket shape).  On a
TPU the base pull configuration runs the Pallas megakernel of
``repro.kernels.event_step``; every other feature set, and every backend
but the TPU, runs the vmapped jnp step (``repro.core.fastpath._event_loop``).
:func:`event_step_path` says which of the two a feature set gets.
``force`` overrides the choice for kernel validation tests
(``interpret=True`` runs the Pallas kernel body in Python on CPU).
"""

from __future__ import annotations

import jax


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def event_step(clk, ctr, inp, *, force: str | None = None,
               interpret: bool = False, **static):
    """Batched fused cluster event scan -- the simulator hot path.

    ``clk``/``ctr`` are the ``(B, len_f)`` / ``(B, len_i)`` packed carry
    plane pairs (see ``repro.core.fastpath._PlaneLayout``) and ``inp`` a
    dict of batched per-cell input arrays; ``static`` carries the kernel's
    compile-time shape/feature kwargs.  Returns the per-cell results,
    batched (see ``repro.core.fastpath._cell_summary``).

    The pure-jnp oracle (``repro.core.fastpath._event_loop``: one loop over
    the batch, its body the per-cell step vmapped, stopped once every cell
    is idle) *is* the fused CPU path -- XLA fuses the plane
    unpack/update/pack chain into the step body.  On TPU the base pull
    configuration runs as a Pallas megakernel with the carry planes
    resident in VMEM across the scan
    (``repro.kernels.event_step``); unsupported feature combinations fall
    back to the oracle unless ``force="pallas"``."""
    from ..core import fastpath as _fp     # lazy: core is heavy

    if event_step_path(force=force, **static) == "pallas":
        from .event_step import event_step_pallas, event_step_supported

        if not event_step_supported(**static):
            raise NotImplementedError(
                "the Pallas event_step covers only the base pull "
                "configuration (no freeze/dyn/het/hedge/cold/dup)")
        return event_step_pallas(clk, ctr, inp,
                                 interpret=interpret or not _on_tpu(),
                                 **static)
    return _fp._event_loop(clk, ctr, inp, **static)


def event_step_path(force: str | None = None, **static) -> str:
    """Which implementation :func:`event_step` runs for this static feature
    set on the current backend: ``"pallas"`` (the megakernel; TPU, base
    pull) or ``"jnp"`` (the vmapped oracle)."""
    from .event_step import event_step_supported

    if force == "ref":
        return "jnp"
    if force == "pallas" or (_on_tpu() and event_step_supported(**static)):
        return "pallas"
    return "jnp"
