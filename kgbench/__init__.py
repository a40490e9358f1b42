"""On-chip benchmark of the knowledge-graph query server (see run.py)."""
