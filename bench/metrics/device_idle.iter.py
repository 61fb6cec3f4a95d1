"""Device idle share of the traced window of the cells that report
``iteration_s`` (``bench.readers.device_idle``)."""
from bench.readers import device_idle as read  # noqa: F401
