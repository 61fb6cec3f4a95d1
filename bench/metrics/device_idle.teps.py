"""Device idle share of the traced window of the cells that report
``teps`` (``bench.readers.device_idle``)."""
from bench.readers import device_idle as read  # noqa: F401
