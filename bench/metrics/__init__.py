"""Per-layer metric readers, one file per metric, named as in
``BENCHMARK.json``: each defines ``read(ctx)`` and returns ``None`` where
its cell has nothing for it to read."""
