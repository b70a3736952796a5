"""Jit'd dispatch wrappers: Pallas on TPU, jnp oracle elsewhere.

The model code calls these entry points; on a real TPU the Pallas kernels
run (interpret=False), on CPU (this container, and all tests) the pure-jnp
references execute.  ``force`` overrides for kernel validation tests
(interpret=True runs the Pallas kernel body in Python on CPU).
"""

from __future__ import annotations

import jax

from . import ref
from .decode_attention import decode_attention as _decode_pallas
from .flash_attention import flash_attention as _flash_pallas
from .rglru_scan import rglru_scan as _rglru_pallas
from .rwkv6_scan import rwkv6_scan as _rwkv6_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def flash_attention(q, k, v, *, causal=True, window=-1, softmax_scale=None,
                    force: str | None = None, interpret: bool = False):
    """force: None (auto) | "pallas" | "ref"."""
    use_pallas = force == "pallas" or (force is None and _on_tpu())
    if use_pallas:
        return _flash_pallas(q, k, v, causal=causal, window=window,
                             softmax_scale=softmax_scale,
                             interpret=interpret or not _on_tpu())
    return ref.attention_ref(q, k, v, causal=causal, window=window,
                             softmax_scale=softmax_scale)


def decode_attention(q, k, v, lengths, *, softmax_scale=None,
                     force: str | None = None, interpret: bool = False):
    use_pallas = force == "pallas" or (force is None and _on_tpu())
    if use_pallas:
        return _decode_pallas(q, k, v, lengths, softmax_scale=softmax_scale,
                              interpret=interpret or not _on_tpu())
    return ref.decode_attention_ref(q, k, v, lengths,
                                    softmax_scale=softmax_scale)


def rglru_scan(a, gx, h0, *, force: str | None = None,
               interpret: bool = False):
    use_pallas = force == "pallas" or (force is None and _on_tpu())
    if use_pallas:
        return _rglru_pallas(a, gx, h0, interpret=interpret or not _on_tpu())
    return ref.rglru_ref(a, gx, h0)


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


def rwkv6_scan(r, k, v, w, u, *, force: str | None = None,
               interpret: bool = False):
    use_pallas = force == "pallas" or (force is None and _on_tpu())
    if use_pallas:
        return _rwkv6_pallas(r, k, v, w, u,
                             interpret=interpret or not _on_tpu())
    import jax.numpy as jnp
    s0 = jnp.zeros((r.shape[0], r.shape[2], r.shape[3], r.shape[3]),
                   jnp.float32)
    out, _ = ref.rwkv6_ref(r, k, v, w, u, s0)
    return out
