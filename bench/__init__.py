"""On-chip benchmark of the repository: see bench/harness.py."""
