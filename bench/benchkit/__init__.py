"""The benchmark's own code: traffic, weights, trace reduction, metrics
arithmetic, the comparison that decides ``correct``, and the run itself.

It imports the system under test only in :mod:`benchkit.runner` and in the
adapters under ``bench/systems/``; everything else here is the yardstick.
"""
