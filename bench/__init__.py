"""On-chip serving benchmark: harness, traffic, reference and metrics."""
