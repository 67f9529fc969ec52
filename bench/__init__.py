"""Chip benchmark of the MoE serving path: see bench/run.py."""
