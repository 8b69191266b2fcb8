"""On-chip benchmark of the vector-search serve path (``python bench/run.py``)."""
