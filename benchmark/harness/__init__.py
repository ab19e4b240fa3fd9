"""The benchmark's yardstick: traffic, reference, trace reduction, peaks.

Nothing here imports the program at module level.  Drivers
(``harness/drivers/<kind>.py``) import the system under test when they
run; the reference (``reference.py``) never does.
"""
