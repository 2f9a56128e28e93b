"""Chip benchmark of the W1A8 detector server (see PERF.md)."""
