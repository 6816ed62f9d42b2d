"""On-chip benchmark of the lazy elastic-net system (see ``chipbench/run.py``)."""
