"""On-chip benchmark of the tiered key-value store (see ``run.py``)."""
