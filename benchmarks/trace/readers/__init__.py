"""One reader per file, found by the name a ``layer_metrics/<metric>.json``
gives. ``read(ctx, **args)`` returns a number, or None when there is nothing
to read (the harness then leaves the metric out of the line). ``ctx.trace`` is
the reduced profiler trace (None when the run was not traced), ``ctx.window``
the traced window ``(lo, hi)`` and ``ctx.facts`` what the harness counted and
clocked itself (see drivers/train.py ``facts``)."""
