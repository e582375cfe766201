"""The on-chip benchmark: harness, yardstick and data (see PERF.md)."""
