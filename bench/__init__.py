"""The chip benchmark of the tiled GP pipeline (see ``BENCHMARK.json``)."""
