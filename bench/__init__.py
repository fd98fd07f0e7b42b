"""Chip benchmark of the MoE trainer and server; see ``bench/run.py``."""
