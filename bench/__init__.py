"""On-chip benchmark of the RoCoIn serving path (see ``bench/run.py``)."""
