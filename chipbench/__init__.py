"""On-chip benchmark of the served prefill and decode step (see run.py)."""
