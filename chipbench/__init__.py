"""On-chip benchmark of the serving path: cells, traffic, metrics."""
