"""On-chip benchmark of the Halo serving path; see ``run.py``."""
