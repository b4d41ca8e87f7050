"""Chip benchmark of the served GCN path (see ``bench/README.md``)."""
