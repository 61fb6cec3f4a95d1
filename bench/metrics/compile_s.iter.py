"""Compile seconds in the window of the cells that report ``iteration_s``
(``bench.readers.compile_s``)."""
from bench.readers import compile_s as read  # noqa: F401
