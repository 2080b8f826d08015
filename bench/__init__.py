"""The chip benchmark: one command, data files found by name, and the
reduction from traces and clocks to metrics (see ``run.py``)."""
