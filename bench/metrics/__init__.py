"""One reader per metric: `<metric>.py` defines ``read(ctx)`` and
``READS``, the spans, counters or trace names it reads. A reader that
finds nothing to read returns None, and the run leaves the metric out."""
