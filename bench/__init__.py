"""On-chip benchmark of the MATADOR TM system (see ``bench/run.py``)."""
