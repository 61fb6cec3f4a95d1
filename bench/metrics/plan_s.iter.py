"""Seconds per PageRank iteration in the host span ``engine.plan``: its
seconds in the traced window over the window's iterations
(``bench.spans``)."""
from bench.spans import reader

read = reader("spans", "engine.plan")
