"""Traffic for the benchmark: mix files (``<name>.json``), the laws they
name (``laws/<category>.<law>.py``) and the one generator that reads
them (``generator.py``), with the field they are drawn over
(``field.py``)."""
