"""The chip benchmark of the serving engine (see PERF.md)."""
