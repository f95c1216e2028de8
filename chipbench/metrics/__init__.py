"""One reader per metric: ``read(record)`` returns the metric's value from
a ``harness.Record``, or ``None`` where the run gives it nothing to read.
``harness.reader`` finds the file by the metric's name."""
