"""The chip benchmark of the routed serving path (see ``run.py``)."""
