"""Per-layer metric readers: ``<metric>.py`` holds ``read(readings)``,
returning the metric's value or ``None`` where there is nothing to read."""
