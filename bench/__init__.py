"""On-chip benchmark of the HFL system: one cell per process (see run.py)."""
