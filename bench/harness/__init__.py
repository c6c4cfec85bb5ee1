"""The benchmark harness: what every cell shares, the correctness checks,
trace reduction and work counts. The runner of each kind of traffic is
in ``bench/kinds/``. Run through ``bench/run.py``."""
