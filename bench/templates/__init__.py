"""Statement templates, one module each, found by the name a traffic file
gives.  A template has ``SMALL`` (every answer of the window is checked, not
one round's), ``LIMITS`` (its compared numbers and their limits),
``prepare(host, params)`` (set-up: resolves literals from the data),
``run(tables, params)`` (builds and collects the statement on the engine),
``reference(host, params, lowp)`` (the plain answer; in bfloat16 with
``lowp``, the control) and ``compare(got, want)`` (the numbers)."""
