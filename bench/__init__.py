"""On-chip benchmark of the federation engine (see ``bench/run.py``)."""
