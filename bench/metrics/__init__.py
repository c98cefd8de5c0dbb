"""Per-layer metric readers, one module per metric of ``BENCHMARK.json``'s
``per_layer``, found by the metric's name.  Each has ``read(window)``
returning the number, or None where the window holds nothing to read
(``harness.Window``: statements, ExecStats deltas, engine spans, compile
counts, the device trace and the recorded kernel calls)."""
