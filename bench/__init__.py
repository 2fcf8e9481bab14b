"""The chip benchmark: cells, drivers, metrics and the plain references (see bench/run.py)."""
