"""The simulator's event-step kernels.

``ops.py`` holds the dispatch (:func:`ops.event_step`, with
:func:`ops.event_step_path` naming the implementation it picks): the
Pallas megakernel of ``event_step.py`` on a TPU for the base pull
configuration, the vmapped jnp step of ``repro.core.fastpath`` everywhere
else.  Callers import the modules directly.
"""
