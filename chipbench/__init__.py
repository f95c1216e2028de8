"""On-chip benchmark of the served image pipelines (see ``run.py``)."""
