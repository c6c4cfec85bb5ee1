"""Configurations of the benchmark (``<name>.json``) and the plain
reference they are checked against (``psvgp_reference.py``)."""
