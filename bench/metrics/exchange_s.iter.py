"""Seconds per PageRank iteration in the device scope ``exchange``: its
seconds in the traced window over the window's iterations
(``bench.spans``)."""
from bench.spans import reader

read = reader("scopes", "exchange")
