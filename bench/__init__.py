"""On-chip benchmark of the preemptive serving engine (see PERF.md)."""
