"""Metric readers: ``<metric>.py`` defines ``read(w: harness.Window)``."""
