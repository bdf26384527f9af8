"""Chip benchmark of the densest-subgraph serving path (``python bench/run.py``)."""
