"""The chip benchmark of the FL experiment engine (see BENCHMARK.json)."""
