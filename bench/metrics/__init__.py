"""Per-layer metric readers, one module per metric, found by the metric's
name in ``BENCHMARK.json``.  Each defines ``read(ctx)`` and returns the
number, or ``None`` when the run has nothing for it to read.

``ctx`` carries what a run measured:

* ``calls``: calls the program completed in the window;
* ``traced_calls``: calls completed in the traced span, the window's first
  blocks;
* ``records``: the program's ``scan_bucket_timings()`` records of the window;
* ``window_compiles``: JAX compilations inside the window, a backend
  compile or a load from the persistent cache each;
* ``trace``: the reduced profiler trace (``trace_reduce.Summary``) of the
  traced span, or ``None`` in an untraced run;
* ``clock_bytes``: the clock width of the cell, 4 or 8;
* ``peaks``: the device's entry in ``peaks.json``;
* ``peak_bytes``: the device's peak bytes in use, or ``None``.
"""
