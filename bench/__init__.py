"""The on-chip benchmark of RDF-h: `python3 bench/run.py`, see `run.py`."""
