"""On-chip benchmark of the GNN training system (see ``harness.py``)."""
