"""On-chip benchmark of the fused flow-serving path (see README.md)."""
