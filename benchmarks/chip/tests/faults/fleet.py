"""Faults of the ``fleet`` entry: those of the detector offload path."""
from faults.detector import plant  # noqa: F401
