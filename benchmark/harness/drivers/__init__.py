"""Drivers, one per cell ``kind``, each with ``run(run) -> dict``."""
