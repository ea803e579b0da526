"""The benchmark's yardstick: traffic generation, window arithmetic, the
peak table, the profiler-trace reduction, and what every model's seeded
weights and plain reference share; each architecture's shape, weights,
reference and work counts are its module in ``bench/models/``. It imports
nothing of the program under test except through ``driver.py``, which
drives the serving engine through its API, and a model module's
``program_config``."""
