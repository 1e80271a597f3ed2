"""Hand-made traces for the reduction tests."""


def empty_xspace() -> bytes:
    """An ``XSpace`` with one host plane named ``/host:CPU`` and no lines."""
    name = b"/host:CPU"
    plane = bytes([0x12, len(name)]) + name  # XPlane.name = 2
    return bytes([0x0A, len(plane)]) + plane  # XSpace.planes = 1
