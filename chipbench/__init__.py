"""Chip benchmark of the shared-cache simulator and the working-set
estimator: cells, configurations, traffic, metrics and the plain
references that decide ``correct``. Run one cell with ``run.py``."""
