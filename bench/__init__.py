"""Chip benchmark of the SPARQL serving path (see ``bench/run.py``)."""
