"""On-chip benchmark of local-SGD training (see ``run.py``)."""
