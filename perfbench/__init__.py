"""Chip benchmark of the RASA simulator's design sweeps (see ``run.py``)."""
