"""One reader per per-layer metric: read(record) -> number, or None where
there is nothing to read (the harness then leaves the metric out of the
line). Layer, unit and `moves` are the manifest's."""
