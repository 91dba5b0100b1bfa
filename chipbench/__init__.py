"""Chip benchmark of the serving stack: open-loop cells driven by data
files (see ``bench.py``); ``run.py`` is the command."""
