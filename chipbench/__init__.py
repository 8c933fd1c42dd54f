"""Chip benchmark of the repo's CNN serving and training paths
(``python3 chipbench/run.py --help``)."""
