"""The benchmark's yardstick: traffic generation, window arithmetic, needed
work, the peak table, the profiler-trace reduction, the seeded weights and
the plain reference. It imports nothing of the program under test except
through ``driver.py``, which drives the serving engine through its API."""
